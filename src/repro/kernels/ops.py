"""Jit'd public wrappers for the Pallas kernels.

Whether a kernel runs compiled or in Pallas interpret mode is decided in
ONE place, ``interpret_mode()``: interpret mode (the kernel body executed
op by op, for validation) on the CPU backend only.  Every other backend
runs the compiled kernel, so a kernel that the chip's compiler refuses
fails loudly there instead of falling back.  The flag is a static jit
argument, so a change of backend can never reuse a trace of the other
mode.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention as _flash_attn
from repro.kernels.flash_decode import (flash_decode as _flash_decode,
                                        flash_decode_partial as _fd_partial)
from repro.kernels.paged_decode import (paged_flash_decode as _paged_decode,
                                        paged_flash_verify as _paged_verify)
from repro.kernels.streamed_matmul import (quantized_matmul as _qmatmul,
                                           streamed_matmul as _matmul)


def interpret_mode() -> bool:
    """True only on the CPU backend, where Pallas TPU kernels cannot be
    compiled and run in interpret mode instead."""
    return jax.default_backend() == "cpu"


# ---- autotuned defaults (kernels/autotune.py) ----------------------------
# ``set_tuned`` installs per-device tile selections; the wrappers resolve
# their default blocks from here, falling back to the built-ins whenever a
# tuned tile does not divide the call's shape (the kernels require
# divisible tiling after clamping).
_DEFAULT_TILES = {"block_m": 256, "block_n": 256, "block_k": 512}
_TUNED: dict = {"matmul": None, "quant_matmul": None, "paged_impl": None}


def set_tuned(*, matmul=None, quant_matmul=None,
              paged_impl: Optional[str] = None):
    """Install autotune selections as process-wide wrapper defaults
    (pass nothing to clear)."""
    _TUNED["matmul"] = dict(matmul) if matmul else None
    _TUNED["quant_matmul"] = dict(quant_matmul) if quant_matmul else None
    _TUNED["paged_impl"] = paged_impl


def tuned_paged_impl() -> Optional[str]:
    """The autotuned paged-decode impl choice ("pallas" / "reference"),
    or None when untuned — ``core.modules.resolve_attn_impl`` consults
    this for ``attn_impl="auto"``."""
    return _TUNED["paged_impl"]


def _divides(tile: dict, m: int, k: int, n: int) -> bool:
    bm = min(tile["block_m"], m)
    bn = min(tile["block_n"], n)
    bk = min(tile["block_k"], k)
    return m % bm == 0 and n % bn == 0 and k % bk == 0


def _resolve_tiles(kernel: str, m: int, k: int, n: int, block_m, block_n,
                   block_k) -> dict:
    tuned = _TUNED[kernel]
    base = (tuned if tuned is not None and _divides(tuned, m, k, n)
            else _DEFAULT_TILES)
    return {"block_m": block_m if block_m is not None else base["block_m"],
            "block_n": block_n if block_n is not None else base["block_n"],
            "block_k": block_k if block_k is not None else base["block_k"]}


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def _matmul_jit(x, w, *, block_m: int, block_n: int, block_k: int,
                interpret: bool):
    return _matmul(x, w, block_m=block_m, block_n=block_n, block_k=block_k,
                   interpret=interpret)


def matmul(x, w, *, block_m: Optional[int] = None,
           block_n: Optional[int] = None, block_k: Optional[int] = None):
    tiles = _resolve_tiles("matmul", x.shape[0], x.shape[1], w.shape[1],
                           block_m, block_n, block_k)
    return _matmul_jit(x, w, **tiles, interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("bits", "block_m", "block_n",
                                             "block_k", "interpret"))
def _quant_matmul_jit(x, w_q, scale, *, bits: int, block_m: int,
                      block_n: int, block_k: int, interpret: bool):
    return _qmatmul(x, w_q, scale, bits=bits, block_m=block_m,
                    block_n=block_n, block_k=block_k, interpret=interpret)


def quant_matmul(x, w_q, scale, *, bits: int = 8,
                 block_m: Optional[int] = None,
                 block_n: Optional[int] = None,
                 block_k: Optional[int] = None):
    """Fused dequant-matmul over int8/int4 per-channel-scaled weights."""
    k = x.shape[1]
    tiles = _resolve_tiles("quant_matmul", x.shape[0], k, w_q.shape[1],
                           block_m, block_n, block_k)
    if bits == 4 and min(tiles["block_k"], k) % 2:
        tiles["block_k"] = _DEFAULT_TILES["block_k"]
    return _quant_matmul_jit(x, w_q, scale, bits=bits, **tiles,
                             interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def _attention_jit(q, k, v, *, causal, window, block_q, block_k, interpret):
    return _flash_attn(q, k, v, causal=causal, window=window,
                       block_q=block_q, block_k=block_k, interpret=interpret)


def attention(q, k, v, *, causal: bool = True,
              window: Optional[int] = None, block_q: int = 256,
              block_k: int = 256):
    return _attention_jit(q, k, v, causal=causal, window=window,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret_mode())


_decode_jit = jax.jit(_flash_decode, static_argnames=("block_k", "interpret"))
_decode_partial_jit = jax.jit(_fd_partial,
                              static_argnames=("block_k", "interpret"))
_paged_decode_jit = jax.jit(_paged_decode, static_argnames=("interpret",))
_paged_verify_jit = jax.jit(_paged_verify, static_argnames=("interpret",))


def decode(q, k, v, valid, *, block_k: int = 512):
    return _decode_jit(q, k, v, valid, block_k=block_k,
                       interpret=interpret_mode())


def decode_partial(q, k, v, valid, *, block_k: int = 512):
    return _decode_partial_jit(q, k, v, valid, block_k=block_k,
                               interpret=interpret_mode())


def paged_decode(q, k_pages, v_pages, tables, lengths):
    """Paged flash decode through per-row block tables, directly over
    the scheduler's (P, page, KV, dh) physical pool layout (tile size
    is the pool's page size; no relayout or densify)."""
    return _paged_decode_jit(q, k_pages, v_pages, tables, lengths,
                             interpret=interpret_mode())


def paged_verify(q, k_pages, v_pages, tables, lengths):
    """Stacked multi-query paged decode (speculative verify): q is
    (B, W, KV, G, dh), query i of row b attends slots
    ``<= lengths[b] - W + i`` — one call scores a whole speculation
    window against the block-table pool."""
    return _paged_verify_jit(q, k_pages, v_pages, tables, lengths,
                             interpret=interpret_mode())
