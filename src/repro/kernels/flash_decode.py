"""Flash-decoding Pallas kernel: one query token vs. a long KV cache.

This is the per-shard compute of the sequence-sharded decode path
(models/attention.flash_decode): the grid dim over cache blocks streams the
KV cache HBM -> VMEM (decode is memory-bound; the pipeline keeps the MXU/VPU
fed — PIPELOAD's overlap where it matters most).  Emits unnormalised
(o, m, l) partials so the cross-shard softmax combine (psum/pmax) can merge
shards exactly like the in-kernel running stats.

Layout: q (BH, dh); k/v (BH, S, dh); valid (BH, S) bool.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_out_ref,
                   l_out_ref, m_ref, l_ref, acc_ref, *, n_k: int,
                   scale: float):
    kk = pl.program_id(1)

    @pl.when(kk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...] * scale                                  # (1, dh)
    s = jnp.dot(q, k_ref[...].T,
                preferred_element_type=jnp.float32)         # (1, bk)
    s = jnp.where(valid_ref[...], s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))   # (1, 1)
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = (acc_ref[...] * corr
                    + jnp.dot(p.astype(v_ref.dtype), v_ref[...],
                              preferred_element_type=jnp.float32))

    @pl.when(kk == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)       # unnormalised
        m_out_ref[...] = m_ref[...]
        l_out_ref[...] = l_ref[...]


def flash_decode_partial(q: jax.Array, k: jax.Array, v: jax.Array,
                         valid: jax.Array, *, block_k: int = 512,
                         interpret: bool = False):
    """Returns unnormalised (o (BH, dh) f32, m (BH, 1), l (BH, 1))."""
    bh, dh = q.shape
    s = k.shape[1]
    bk = min(block_k, s)
    assert s % bk == 0, (s, bk)
    n_k = s // bk
    scale = 1.0 / (dh ** 0.5)

    kern = functools.partial(_decode_kernel, n_k=n_k, scale=scale)
    # one BH row per grid step, squeezed out of every block: q, valid and
    # the outputs ride as (BH, 1, x) so each block's last two dims meet
    # the TPU's (8, 128)-or-full rule (bk must then be the whole cache
    # or a multiple of 128 when compiled for the chip)
    row = lambda b, kk: (b, 0, 0)                          # noqa: E731
    o, m, l = pl.pallas_call(
        kern,
        grid=(bh, n_k),
        in_specs=[
            pl.BlockSpec((None, 1, dh), row),
            pl.BlockSpec((None, bk, dh), lambda b, kk: (b, kk, 0)),
            pl.BlockSpec((None, bk, dh), lambda b, kk: (b, kk, 0)),
            pl.BlockSpec((None, 1, bk), lambda b, kk: (b, 0, kk)),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, dh), row),
            pl.BlockSpec((None, 1, 1), row),
            pl.BlockSpec((None, 1, 1), row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, 1, dh), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, dh), jnp.float32),
        ],
        interpret=interpret,
    )(q.reshape(bh, 1, dh), k, v, valid.reshape(bh, 1, s))
    return o.reshape(bh, dh), m.reshape(bh, 1), l.reshape(bh, 1)


def flash_decode(q, k, v, valid, *, block_k: int = 512,
                 interpret: bool = False):
    """Normalised single-shard decode: (BH, dh)."""
    o, m, l = flash_decode_partial(q, k, v, valid, block_k=block_k,
                                   interpret=interpret)
    return (o / jnp.maximum(l, 1e-30)).astype(v.dtype)
