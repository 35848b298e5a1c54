"""Per-channel symmetric weight quantization for PIPELOAD shards.

Hermes' wins come from shrinking what must be resident and overlapping
loads with compute — but the disk -> memory weight path bounds edge
throughput, so every byte a shard does NOT carry is load time and ledger
headroom won back.  This module defines the on-disk and in-memory form of
int8/int4 shards:

  * **scheme** — symmetric per-output-channel scaling: a 2-D float weight
    ``W (K, N)`` becomes ``q = clip(round(W / scale), -qmax, qmax)`` with
    ``scale (N,) = max|W[:, j]| / qmax`` (int8: qmax=127, int4: qmax=7).
    1-D params (norms, biases) stay in the checkpoint dtype — they are
    a rounding error of the byte total and accuracy-critical.
  * **int4 packing** — two values per byte along the K axis (row ``2i``
    in the low nibble, ``2i+1`` in the high nibble), so an int4 shard is
    ~1/8 the fp32 bytes plus the f32 scale vector.
  * **in-memory form** — ``QuantizedTensor``, a registered pytree whose
    leaves are the integer payload + scales.  The ledger accounts these
    quantized bytes; dequantization happens *inside* the jitted module
    fns (or in-kernel via ``kernels.streamed_matmul.quantized_matmul``),
    so the fp copy of at most the layer being computed is transient and
    never resident between rounds.

``quantize_flat`` / ``restore_tree`` are the serialisation halves used
by ``checkpoint/partition.py``'s raw shard store: a quantized array at
flat key ``k`` is stored as ``k.__q__`` / ``k.__scale__`` /
``k.__meta__`` arrays in the shard file and a ``k.__dtype__`` string in
the shard's tensor table, so the existing dotted-key unflattening nests
them into a dict that ``restore_tree`` folds back into a
``QuantizedTensor``.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# quant name -> (bits, qmax)
QUANT_SCHEMES: Dict[str, Tuple[int, int]] = {"int8": (8, 127), "int4": (4, 7)}
SCHEME = "symmetric-per-channel"

_Q, _SCALE, _META, _DTYPE = "__q__", "__scale__", "__meta__", "__dtype__"


def qmax_for(bits: int) -> int:
    return 127 if bits == 8 else 7


@jax.tree_util.register_pytree_node_class
class QuantizedTensor:
    """Integer weight + per-channel scales; ``dequantize()`` reconstructs.

    ``q`` is int8 for 8-bit, or uint8 nibble-packed along axis 0 for
    4-bit; ``scale`` is float32 ``(N,)``; ``shape`` is the original
    (unpacked) shape and ``dtype`` the original float dtype name.  Being
    a pytree with static (bits, shape, dtype) aux data, it passes
    through ``jax.tree.map(jnp.asarray, ...)`` and jitted module fns
    unchanged — the engine keeps the *quantized* form resident.
    """

    def __init__(self, q, scale, bits: int, shape: Tuple[int, ...],
                 dtype: str):
        self.q = q
        self.scale = scale
        self.bits = int(bits)
        self.shape = tuple(int(s) for s in shape)
        self.dtype = str(dtype)

    def tree_flatten(self):
        return (self.q, self.scale), (self.bits, self.shape, self.dtype)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    @property
    def nbytes(self) -> int:
        """Resident bytes: payload + scales (what the ledger charges)."""
        return int(self.q.nbytes + self.scale.nbytes)

    def unpacked(self) -> jax.Array:
        """Integer values at the original shape (int8 even for 4-bit)."""
        q = jnp.asarray(self.q)
        if self.bits == 4:
            q = unpack_int4(q, self.shape[0])
        return q

    def dequantize(self) -> jax.Array:
        return (self.unpacked().astype(jnp.float32)
                * jnp.asarray(self.scale)).astype(self.dtype)

    def take_rows(self, idx) -> jax.Array:
        """Dequantized gather of rows (embedding lookup fast path): for
        8-bit, gather the int payload then scale — the full fp table is
        never materialised."""
        if self.bits == 8:
            rows = jnp.asarray(self.q)[idx]
            return (rows.astype(jnp.float32)
                    * jnp.asarray(self.scale)).astype(self.dtype)
        return self.dequantize()[idx]

    def __repr__(self):
        return (f"QuantizedTensor(int{self.bits}, shape={self.shape}, "
                f"dtype={self.dtype})")


def is_quantized(leaf) -> bool:
    return isinstance(leaf, QuantizedTensor)


# ---------------------------------------------------------------------------
# int4 nibble packing (axis 0, row 2i low nibble / row 2i+1 high nibble)
# ---------------------------------------------------------------------------
def pack_int4(q: np.ndarray) -> np.ndarray:
    """(K, N) int values in [-8, 7] -> (ceil(K/2), N) uint8."""
    k = q.shape[0]
    if k % 2:
        q = np.concatenate([q, np.zeros((1,) + q.shape[1:], q.dtype)])
    lo = (q[0::2] & 0xF).astype(np.uint8)
    hi = (q[1::2] & 0xF).astype(np.uint8)
    return lo | (hi << 4)


def unpack_int4(packed, rows: int):
    """Inverse of ``pack_int4`` (jnp: used inside jitted dequant and the
    ``quantized_matmul`` kernel).  The nibbles are shifted and masked in
    int32: Mosaic cannot lower shifts on 8-bit integers."""
    p = jnp.asarray(packed).astype(jnp.uint8).astype(jnp.int32)
    lo = p & 0xF
    hi = (p >> 4) & 0xF
    lo = jnp.where(lo > 7, lo - 16, lo)
    hi = jnp.where(hi > 7, hi - 16, hi)
    full = jnp.stack([lo, hi], axis=1).reshape((-1,) + p.shape[1:])
    return full[:rows].astype(jnp.int8)


# ---------------------------------------------------------------------------
# Quantize / dequantize arrays
# ---------------------------------------------------------------------------
def quantizable(a: np.ndarray, key: Optional[str] = None) -> bool:
    """Only the 2-D matmul weights carry the bytes worth shrinking.

    MoE routers are exempt even though they are 2-D: a router is a
    rounding error of the byte total (d_model x n_experts) but
    routing-CRITICAL — an int8 rounding flip changes the top-k expert
    set discretely, which moves whole experts' worth of output, not an
    epsilon.  Keeping it at checkpoint dtype keeps quantized MoE token
    selection aligned with fp32 routing."""
    if key is not None and key.split(".")[-1] == "router":
        return False
    a = np.asarray(a)
    return a.ndim == 2 and jnp.issubdtype(a.dtype, jnp.floating)


def quantize_array(a, quant: str) -> QuantizedTensor:
    bits, qmax = QUANT_SCHEMES[quant]
    dtype = str(jnp.asarray(a).dtype)
    a32 = np.asarray(a).astype(np.float32)
    amax = np.abs(a32).max(axis=0)
    scale = np.where(amax > 0, amax / qmax, 1.0).astype(np.float32)
    q = np.clip(np.rint(a32 / scale), -qmax, qmax).astype(np.int8)
    payload = pack_int4(q) if bits == 4 else q
    return QuantizedTensor(payload, scale, bits, a32.shape, dtype)


def dequant_tree(tree):
    """Map QuantizedTensor leaves back to float arrays (jit-safe); plain
    arrays pass through untouched."""
    return jax.tree.map(
        lambda leaf: leaf.dequantize() if is_quantized(leaf) else leaf,
        tree, is_leaf=is_quantized)


# ---------------------------------------------------------------------------
# (de)serialisation of flat {dotted_key: array} shard dicts
# ---------------------------------------------------------------------------
def quantize_flat(flat: Dict[str, np.ndarray],
                  quant: Optional[str]) -> Dict[str, np.ndarray]:
    """Replace every quantizable array in a flat shard dict with its
    ``__q__/__scale__/__meta__`` arrays and ``__dtype__`` string."""
    if quant is None:
        return dict(flat)
    out: Dict[str, np.ndarray] = {}
    for key, arr in flat.items():
        if quantizable(arr, key):
            qt = quantize_array(arr, quant)
            out[f"{key}.{_Q}"] = np.asarray(qt.q)
            out[f"{key}.{_SCALE}"] = np.asarray(qt.scale)
            out[f"{key}.{_META}"] = np.array([qt.bits, *qt.shape], np.int64)
            out[f"{key}.{_DTYPE}"] = np.str_(qt.dtype)
        else:
            out[key] = arr
    return out


def restore_tree(tree):
    """Fold ``{__q__, __scale__, __meta__, __dtype__}`` dicts (produced
    by unflattening a quantized shard) back into QuantizedTensor leaves."""
    if not isinstance(tree, dict):
        return tree
    if _Q in tree:
        meta = np.asarray(tree[_META])
        return QuantizedTensor(tree[_Q], tree[_SCALE], int(meta[0]),
                               tuple(int(s) for s in meta[1:]),
                               str(tree[_DTYPE]))
    return {k: restore_tree(v) for k, v in tree.items()}
