"""Mixture-of-Experts FFN with capacity-based dispatch + expert parallelism.

Expert parallelism: experts are sharded across the ``model`` mesh axis while
activations stay replicated on it (they already are, between attention
blocks).  Each model shard dispatches tokens to its local experts only and
the per-shard partial outputs are combined with one ``psum`` — the same
collective a Megatron-style TP MLP needs, so MoE composes with the rest of
the sharding scheme with no all-to-all in the baseline.  (An all-to-all
dispatch variant is a recorded §Perf lever.)

Dispatch is sort-based (GShard-style capacity, token dropping) rather than
one-hot-einsum based: the (T, E, C) dispatch tensor is never materialised.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models import common
from repro.models.config import ModelConfig
from repro.sharding import ShardingCtx


def moe_init(key, cfg: ModelConfig) -> dict:
    e, d, f = cfg.n_experts, cfg.d_model, cfg.expert_d_ff
    ks = common.split_keys(key, 4)
    scale = 1.0 / math.sqrt(d)
    fscale = 1.0 / math.sqrt(f)
    p = {
        "router": common.dense_init(ks[0], d, e, jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e, d, f), jnp.float32) * scale
                   ).astype(cfg.jnp_dtype),
        "w_up": (jax.random.normal(ks[2], (e, d, f), jnp.float32) * scale
                 ).astype(cfg.jnp_dtype),
        "w_down": (jax.random.normal(ks[3], (e, f, d), jnp.float32) * fscale
                   ).astype(cfg.jnp_dtype),
    }
    return p


def capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = int(math.ceil(n_tokens * cfg.top_k / cfg.n_experts
                      * cfg.capacity_factor))
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU-friendly shapes


def _dispatch_indices(expert_ids: jax.Array, top_k: int, n_experts: int,
                      cap: int, e0, e_local: int):
    """Pair -> local buffer slot (or OOB = dropped).

    expert_ids: (T, K) int32.  Returns slots (T, K) int32 into a local
    (e_local * cap) buffer; pairs routed to non-local experts or beyond
    capacity map to e_local*cap (out of bounds -> dropped by .at ops).
    """
    t = expert_ids.shape[0]
    flat_e = expert_ids.reshape(-1)                       # (T*K,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    # position of each pair within its expert group (deterministic, global)
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos_sorted = jnp.arange(t * top_k) - first
    pos = jnp.zeros_like(pos_sorted).at[order].set(pos_sorted)
    pos = pos.reshape(t, top_k)

    local_e = expert_ids - e0
    ok = ((local_e >= 0) & (local_e < e_local) & (pos < cap))
    slots = jnp.where(ok, local_e * cap + pos, e_local * cap)
    return slots.astype(jnp.int32)


def _expert_ffn(buf: jax.Array, w_gate, w_up, w_down) -> jax.Array:
    """buf: (E_loc, C, D) -> (E_loc, C, D) via per-expert SwiGLU."""
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate))
    up = jnp.einsum("ecd,edf->ecf", buf, w_up)
    return jnp.einsum("ecf,efd->ecd", gate * up, w_down)


def _moe_local(params, x_flat: jax.Array, cfg: ModelConfig, cap: int,
               e0, e_local: int) -> Tuple[jax.Array, jax.Array]:
    """Dispatch + expert compute for experts [e0, e0+e_local).

    x_flat: (T, D).  Returns (out (T, D) containing ONLY local experts'
    contributions, aux load-balance loss computed over all experts).
    """
    t, d = x_flat.shape
    k = cfg.top_k
    logits = (x_flat.astype(jnp.float32) @ params["router"])   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_ids = jax.lax.top_k(probs, k)                   # (T, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balance statistics (combined into the aux loss by
    # the caller AFTER cross-shard averaging, so local and sharded paths
    # produce identical losses).
    e = cfg.n_experts
    frac = jnp.zeros((e,), jnp.float32).at[top_ids.reshape(-1)].add(1.0)
    frac = frac / (t * k)
    p_mean = probs.mean(0)
    zloss = jnp.mean(jax.nn.logsumexp(logits, -1) ** 2) * 1e-3
    stats = (frac, p_mean, zloss)

    slots = _dispatch_indices(top_ids, k, e, cap, e0, e_local)  # (T, K)
    buf = jnp.zeros((e_local * cap, d), x_flat.dtype)
    # scatter pairs into the capacity buffer (dropped pairs fall off the end)
    tok_rep = jnp.broadcast_to(jnp.arange(t)[:, None], (t, k)).reshape(-1)
    buf = buf.at[slots.reshape(-1)].set(x_flat[tok_rep], mode="drop")
    buf = _expert_ffn(buf.reshape(e_local, cap, d),
                      params["w_gate"], params["w_up"], params["w_down"])
    buf = buf.reshape(e_local * cap, d)

    # combine: loop over K keeps the peak at (T, D)
    def body(acc, kk):
        contrib = buf.at[slots[:, kk]].get(mode="fill", fill_value=0.0)
        return acc + contrib * top_w[:, kk, None].astype(buf.dtype), None

    # carry derived from x_flat AND buf so its varying-axes type matches the
    # body output under shard_map (buf is model-varying via axis_index; a
    # fresh constant would be device-invariant and trip the VMA check)
    acc0 = (x_flat * 0).astype(buf.dtype) + buf[:1] * 0
    out, _ = jax.lax.scan(body, acc0, jnp.arange(k))
    return out, stats




def _aux_from_stats(cfg: ModelConfig, stats) -> jax.Array:
    frac, p_mean, zloss = stats
    return cfg.n_experts * jnp.sum(frac * p_mean) + zloss

def moe_apply(params, x: jax.Array, cfg: ModelConfig,
              ctx: Optional[ShardingCtx]) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out (B, S, D), aux scalar)."""
    b, s, d = x.shape

    if ctx is None:
        cap = capacity(cfg, b * s)
        out, stats = _moe_local(params, x.reshape(-1, d), cfg, cap,
                                jnp.int32(0), cfg.n_experts)
        return out.reshape(b, s, d), _aux_from_stats(cfg, stats)

    msize = ctx.model_size
    assert cfg.n_experts % msize == 0, (cfg.n_experts, msize)
    e_local = cfg.n_experts // msize
    t_local = b * s // (ctx.batch_size if ctx.shard_batch else 1)
    cap = capacity(cfg, t_local)
    bs, ax = ctx.batch_spec, ctx.model_axis

    def local(pp, xx):
        bl, sl, dl = xx.shape
        e0 = jax.lax.axis_index(ax) * e_local
        out, stats = _moe_local(pp, xx.reshape(-1, dl), cfg, cap, e0, e_local)
        out = jax.lax.psum(out, ax)
        if ctx.shard_batch:
            # average the per-shard routing statistics BEFORE forming the
            # product so the sharded loss equals the global-view loss
            stats = jax.tree.map(
                lambda a: jax.lax.pmean(a, ctx.batch_axes), stats)
        aux = _aux_from_stats(cfg, stats)
        # aux is computed from model-replicated inputs; make that explicit
        aux = jax.lax.pmean(aux, ax)
        return out.reshape(bl, sl, dl), aux

    param_specs = {
        "router": P(),                       # replicated
        "w_gate": P(ax, None, None),         # experts sharded on model
        "w_up": P(ax, None, None),
        "w_down": P(ax, None, None),
    }
    return shard_map(
        local, mesh=ctx.mesh,
        in_specs=(param_specs, P(bs, None, None)),
        out_specs=(P(bs, None, None), P()))(params, x)
