"""Paged flash-decoding Pallas kernel: block-table K/V gather.

The paged KV cache (core/kv_pages.py) stores K/V in fixed-size pages of
a physical pool; each request's logical sequence is a block table of
page ids.  This kernel runs a row's queries against that paged cache
WITHOUT densifying it: the block table rides in as a scalar-prefetch
operand, so the BlockSpec index_map dereferences ``tables[b, j]`` to
DMA exactly the j-th logical page HBM -> VMEM — the gather happens in
the grid pipeline, not as a jnp ``take`` that materialises a copy of
the pool.

One grid step is one (row, page) pair and covers EVERY kv head: the
K/V block is the whole ``(page, KV, dh)`` page, whose last two dims are
the array's own, as the TPU's (8, 128) block rule requires (a block of
one kv head would slice the second-minor dim to 1).  Each page is read
once per row, with no G-fold re-read across query groups.  In VMEM the
page is swapped to ``(KV, page, dh)`` so both products are batched over
kv heads on the MXU.

Masking is positional: query ``i`` of a W-token window attends to
global slots ``<= lengths[b] - W + i``; slots past that (the tail of
the last mapped page, and any padded table entries — callers pad short
tables with page 0) contribute exact zeros, so the result is identical
to a dense decode over the logically contiguous cache.  Plain decode is
the W = 1 case.

Layout (the scheduler's native pool layout — no flattening):
k_pages/v_pages (P, page, KV, dh); tables (B, NB) int32; lengths (B,)
int32.  Decode takes q (B, KV, G, dh); verify takes q (B, W, KV, G, dh).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(tab_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, n_b: int, page: int, w: int,
                  g: int, scale: float):
    """q_ref/o_ref (KV, R, dh) with R = W * G query rows (row r is
    window position r // G); k_ref/v_ref one (page, KV, dh) page."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...] * scale                                  # (KV, R, dh)
    k = jnp.swapaxes(k_ref[...], 0, 1)                      # (KV, page, dh)
    v = jnp.swapaxes(v_ref[...], 0, 1)
    s = jnp.einsum("krd,kpd->krp", q, k,
                   preferred_element_type=jnp.float32)      # (KV, R, page)
    slot = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if g > 1:
        row = jax.lax.div(row, jnp.int32(g))
    s = jnp.where(slot <= len_ref[b] - w + row, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))   # (KV, R, 1)
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = (acc_ref[...] * corr
                    + jnp.einsum("krp,kpd->krd", p.astype(v.dtype), v,
                                 preferred_element_type=jnp.float32))

    @pl.when(j == n_b - 1)
    def _flush():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = out.astype(o_ref.dtype)


def _paged_call(q, k_pages, v_pages, tables, lengths, *, w: int, g: int,
                interpret: bool):
    """q (B, KV, R, dh) -> (B, KV, R, dh) over the paged pool."""
    b, kv, r, dh = q.shape
    page = k_pages.shape[1]
    nb = tables.shape[1]
    kern = functools.partial(_paged_kernel, n_b=nb, page=page, w=w, g=g,
                             scale=1.0 / (dh ** 0.5))
    page_spec = pl.BlockSpec((None, page, kv, dh),
                             lambda i, j, tab, lens: (tab[i, j], 0, 0, 0))
    row_spec = pl.BlockSpec((None, kv, r, dh),
                            lambda i, j, tab, lens: (i, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                  # tables, lengths
        grid=(b, nb),
        in_specs=[row_spec, page_spec, page_spec],
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((kv, r, 1), jnp.float32),
            pltpu.VMEM((kv, r, 1), jnp.float32),
            pltpu.VMEM((kv, r, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, r, dh), v_pages.dtype),
        interpret=interpret,
    )(tables, lengths, q, k_pages, v_pages)


def paged_flash_verify(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       tables: jax.Array, lengths: jax.Array, *,
                       interpret: bool = False) -> jax.Array:
    """Stacked multi-query paged decode for speculative verification.

    ``q`` is (B, W, KV, G, dh): W consecutive query tokens per row, the
    last of which sits at slot ``lengths[b] - 1`` (K/V for all W already
    written into the pages).  Each query applies its own causal frontier
    ``slot <= lengths[b] - W + i``, so one kernel call scores a whole
    speculation window.  Returns (B, W, KV, G, dh) in ``v_pages``'s
    dtype.
    """
    b, w, kv, g, dh = q.shape
    qr = q.transpose(0, 2, 1, 3, 4).reshape(b, kv, w * g, dh)
    out = _paged_call(qr, k_pages, v_pages, tables, lengths, w=w, g=g,
                      interpret=interpret)
    return out.reshape(b, kv, w, g, dh).transpose(0, 2, 1, 3, 4)


def paged_flash_decode(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       tables: jax.Array, lengths: jax.Array, *,
                       interpret: bool = False) -> jax.Array:
    """Normalised paged decode: (B, KV, G, dh), dtype of ``v_pages``.

    ``tables`` (B, NB) maps each row's logical block j to a physical
    page id; entries past ``ceil(lengths[b] / page)`` are padding (any
    valid page id — their slots are masked).  ``lengths`` (B,) is the
    number of live slots per row (current position + 1).
    """
    return _paged_call(q, k_pages, v_pages, tables, lengths, w=1,
                       g=q.shape[2], interpret=interpret)
