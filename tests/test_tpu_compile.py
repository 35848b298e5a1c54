"""Every Pallas kernel compiles for a TPU v5e.

Interpret mode (the CPU backend's way of running the kernels) accepts
block shapes the chip's compiler refuses, so the kernel==oracle tests
cannot show that a kernel runs on the chip.  These tests compile each
kernel, and the paged decode layer that serves with them, at gpt2-base
widths (16 heads of 64, d_ff 4096) for a DESCRIBED v5e — the TPU
compiler is installed even where no chip is — and check that the
compiled program holds the kernel (``tpu_custom_call``).  Nothing runs.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get
from repro.core.modules import build_module_fns
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode
from repro.kernels.paged_decode import paged_flash_decode, paged_flash_verify
from repro.kernels.streamed_matmul import quantized_matmul, streamed_matmul
from repro.models import dense_lm

CFG = get("gpt2_base")
B, PAGE, TOTAL, W = 4, 16, 80, 5          # 4 requests, 64 + 16 tokens
NB = TOTAL // PAGE
KV, G, DH = CFG.n_kv_heads, CFG.q_heads_per_kv, CFG.head_dim


@pytest.fixture(scope="module")
def no_persistent_cache():
    """Compiles for a described chip are written to the persistent cache
    but cannot be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(no_persistent_cache):
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The program's one interpret switch, steered to the chip's side:
    this process's backend is the CPU, the compile target is not."""
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)


def _spec(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_decode_compiles(one_chip):
    bh = B * KV * G
    text = _compiled_text(
        lambda q, k, v, valid: flash_decode(q, k, v, valid, block_k=TOTAL),
        _spec(one_chip, (bh, DH)), _spec(one_chip, (bh, TOTAL, DH)),
        _spec(one_chip, (bh, TOTAL, DH)),
        _spec(one_chip, (bh, TOTAL), jnp.bool_))
    assert "tpu_custom_call" in text


def _pool_args(one_chip):
    pool = _spec(one_chip, (B * NB + 2, PAGE, KV, DH))
    return (pool, pool, _spec(one_chip, (B, NB), jnp.int32),
            _spec(one_chip, (B,), jnp.int32))


def test_paged_flash_decode_compiles(one_chip):
    text = _compiled_text(paged_flash_decode,
                          _spec(one_chip, (B, KV, G, DH)),
                          *_pool_args(one_chip))
    assert "tpu_custom_call" in text


def test_paged_flash_verify_compiles(one_chip):
    text = _compiled_text(paged_flash_verify,
                          _spec(one_chip, (B, W, KV, G, DH)),
                          *_pool_args(one_chip))
    assert "tpu_custom_call" in text


def test_streamed_matmul_compiles(one_chip):
    m, k, n = 256, CFG.d_model, CFG.d_ff
    text = _compiled_text(streamed_matmul, _spec(one_chip, (m, k)),
                          _spec(one_chip, (k, n)))
    assert "tpu_custom_call" in text


def test_flash_attention_compiles(one_chip):
    """Causal prefill attention over 256 tokens, every query head."""
    q = _spec(one_chip, (CFG.n_heads, 256, DH))
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=128,
                                        block_k=128), q, q, q)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("bits", [8, 4])
def test_quantized_matmul_compiles(one_chip, bits):
    m, k, n = 256, CFG.d_model, CFG.d_ff
    w_dtype, w_rows = (jnp.int8, k) if bits == 8 else (jnp.uint8, k // 2)
    text = _compiled_text(
        lambda x, w, s: quantized_matmul(x, w, s, bits=bits),
        _spec(one_chip, (m, k)), _spec(one_chip, (w_rows, n), w_dtype),
        _spec(one_chip, (n,)))
    assert "tpu_custom_call" in text


def test_layer_decode_paged_compiles(one_chip, compiled_kernels):
    """The serving path's paged decode layer with ``attn_impl="pallas"``:
    the whole jitted layer, kernel included, is accepted for the chip."""
    fns = build_module_fns(CFG, attn_impl="pallas")
    weights = jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda key: dense_lm.layer_init(key, CFG),
                       jax.random.PRNGKey(0)))
    k_pool, v_pool, tables, pos = _pool_args(one_chip)
    text = fns["layer_decode_paged"].lower(
        weights, _spec(one_chip, (B, 1, CFG.d_model)),
        {"k": k_pool, "v": v_pool}, tables, pos).compile().as_text()
    assert "tpu_custom_call" in text
