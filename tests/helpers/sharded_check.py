"""Subprocess helper: multi-device sharding equivalence checks.

Run with XLA_FLAGS=--xla_force_host_platform_device_count=8 (set by the
calling test BEFORE python starts; jax pins the device count at init).
Exits 0 on success, asserts otherwise.
"""
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.data.synthetic import make_batch
from repro.launch.mesh import make_mesh
from repro.models.api import build_model, param_pspecs
from repro.models.config import DENSE, MOE, ModelConfig
from repro.sharding import ShardingCtx


def main():
    assert len(jax.devices()) == 8, jax.devices()
    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = ShardingCtx(mesh=mesh, batch_axes=("data",), model_axis="model")

    # ---- MoE expert-parallel loss == local loss
    cfg = ModelConfig("moe", MOE, 2, 128, 4, 2, 0, 500, head_dim=32,
                      n_experts=8, top_k=2, expert_d_ff=64,
                      capacity_factor=16.0, vocab_pad_to=4,
                      dtype="float32", remat=False)
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg, 4, 16, seed=0)
    loss_local, _ = jax.jit(lambda p, b: api.loss(p, b, None))(params, batch)
    specs = param_pspecs(params, mesh)
    sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                      is_leaf=lambda x: isinstance(x, P))
    params_sh = jax.device_put(params, sh)
    batch_sh = jax.device_put(batch, NamedSharding(mesh, P("data")))
    loss_sh, _ = jax.jit(lambda p, b: api.loss(p, b, ctx))(params_sh,
                                                           batch_sh)
    assert abs(float(loss_local) - float(loss_sh)) < 1e-4, (
        float(loss_local), float(loss_sh))

    # ---- dense decode with seq-sharded cache == local decode
    cfg2 = ModelConfig("d", DENSE, 2, 128, 4, 2, 256, 500, head_dim=32,
                       vocab_pad_to=4, dtype="float32", remat=False)
    api2 = build_model(cfg2)
    p2 = api2.init(jax.random.PRNGKey(1))
    b2 = make_batch(cfg2, 4, 8, seed=1)
    b2.pop("labels")
    _, cache = jax.jit(lambda p, b: api2.prefill(p, b, None))(p2, b2)
    dcache = api2.empty_cache(4, 16)
    dcache = jax.tree.map(lambda e, f: e.at[:, :, :8].set(f), dcache, cache)
    tok = jnp.ones((4, 1), jnp.int32)
    lg_l, _ = jax.jit(lambda p, t, c: api2.decode(p, t, c, 8, None))(
        p2, tok, dcache)
    dcache_sh = jax.device_put(
        dcache, NamedSharding(mesh, P(None, "data", "model")))
    lg_s, _ = jax.jit(lambda p, t, c: api2.decode(p, t, c, 8, ctx))(
        p2, tok, dcache_sh)
    err = float(np.max(np.abs(np.asarray(lg_l) - np.asarray(lg_s))))
    assert err < 1e-4, err

    # ---- train step under sharding: loss finite & close to local
    from repro.launch.stepfns import make_train_step
    from repro.optim import adamw_init
    step_l = jax.jit(make_train_step(api2, None))
    step_s = jax.jit(make_train_step(api2, ctx))
    b3 = make_batch(cfg2, 4, 16, seed=2)
    o_l = step_l(p2, adamw_init(p2), b3)
    o_s = step_s(jax.device_put(p2, jax.tree.map(
        lambda s: NamedSharding(mesh, s), param_pspecs(p2, mesh),
        is_leaf=lambda x: isinstance(x, P))), adamw_init(p2),
        jax.device_put(b3, NamedSharding(mesh, P("data"))))
    assert abs(float(o_l[2]["loss"]) - float(o_s[2]["loss"])) < 1e-4
    print("SHARDED-CHECK-OK")


if __name__ == "__main__":
    main()
