"""Sharding context threaded through model code.

Model code is written in global view; the two hot spots that need explicit
collective control (expert-parallel MoE, sequence-sharded flash-decode) use
``shard_map`` through this context.  ``ctx=None`` (unit tests, single CPU
device) falls back to purely local dense paths.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardingCtx:
    mesh: Mesh
    batch_axes: Tuple[str, ...] = ("data",)   # ("pod","data") when multi-pod
    model_axis: str = "model"
    shard_batch: bool = True                  # False when batch indivisible
    # PartitionSpec tree for ONE layer's params (stacked dim dropped).  When
    # set, layer-scan bodies constrain their param slice back to the storage
    # sharding so remat residuals stay FSDP-sharded instead of keeping the
    # all-gathered weights alive per layer (94 gathered MoE layers = tens of
    # GB of residuals otherwise).
    layer_param_specs: Optional[object] = dataclasses.field(
        default=None, compare=False, hash=False)

    @property
    def batch_spec(self):
        return self.batch_axes if self.shard_batch else None

    @property
    def model_size(self) -> int:
        return self.mesh.shape[self.model_axis]

    @property
    def batch_size(self) -> int:
        n = 1
        for a in self.batch_axes:
            n *= self.mesh.shape[a]
        return n

    def p(self, *specs) -> P:
        return P(*specs)

    def constraint(self, x, *specs):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*specs)))


def constrain(ctx: Optional[ShardingCtx], x, *specs):
    """Apply a sharding constraint when a mesh is present; identity otherwise."""
    if ctx is None:
        return x
    return ctx.constraint(x, *specs)


def constrain_layer_params(ctx: Optional[ShardingCtx], layer_params):
    """FSDP weight regathering INSIDE the layer-scan body.

    With GSPMD annotations alone, the partitioner reshards the whole
    stacked parameter array ONCE before the while loop (a loop-invariant
    all-gather — tens of GB live for a 94-layer MoE).  Doing the data-axis
    all-gather EXPLICITLY via shard_map on the per-layer slice makes the
    gather depend on the loop induction variable, so it cannot be hoisted:
    weights stream layer by layer, exactly the PIPELOAD pattern at the
    pod tier, and remat re-gathers in the backward pass instead of saving
    gathered weights as residuals.
    """
    if ctx is None or ctx.layer_param_specs is None:
        return layer_params
    from jax.sharding import PartitionSpec as _P

    def f(x, spec: _P):
        if not isinstance(spec, _P):
            return x
        entries = list(spec) + [None] * (x.ndim - len(spec))

        def has_data(e):
            return e == "data" or (isinstance(e, tuple) and "data" in e)

        if not any(has_data(e) for e in entries):
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(ctx.mesh, spec))
        axis = next(i for i, e in enumerate(entries) if has_data(e))
        gather_axes = (entries[axis] if isinstance(entries[axis], tuple)
                       else (entries[axis],))
        out_entries = [None if i == axis else a
                       for i, a in enumerate(entries)]

        def gather(w):
            return jax.lax.all_gather(w, gather_axes, axis=axis, tiled=True)

        # replication check off: the checker can't statically prove
        # all-gather output replication, but a full tiled all_gather over
        # 'data' is replicated on that axis by construction
        return jax.shard_map(gather, mesh=ctx.mesh, in_specs=_P(*entries),
                             out_specs=_P(*out_entries),
                             check_vma=False)(x)

    return jax.tree.map(f, layer_params, ctx.layer_param_specs,
                        is_leaf=lambda v: isinstance(v, _P))


def seq_shard(ctx: Optional[ShardingCtx], x):
    """Megatron-style sequence parallelism between layers: activations
    (B, S, D) sharded on the model axis along S.  Keeps the per-layer scan
    carry (the remat residual) at 1/model_size per chip — without this the
    48-62 saved layer inputs alone overflow HBM on the train shape."""
    if ctx is None or x.ndim != 3:
        return x
    if x.shape[1] % ctx.model_size:
        return x
    return ctx.constraint(x, ctx.batch_spec, ctx.model_axis, None)
