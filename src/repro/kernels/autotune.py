"""Per-device kernel autotune cache (tile / impl selection).

The Pallas kernels expose tuning knobs — ``streamed_matmul`` /
``quantized_matmul`` take ``(block_m, block_n, block_k)`` tiles, and the
paged-decode path can run either the block-table Pallas kernel or the
jnp gather reference — whose best settings depend on the device, the
shard dtype and the KV page size.  The profiler already measures what
the knobs trade off (``t_load`` vs ``t_comp``) but nothing cached the
choice, so every process re-ran with the built-in defaults.

This module searches a small feasible candidate space, times each
candidate on the live device, and caches the winner to disk keyed by
``(kernel, arch, dtype, page_size)`` — repeat runs skip the search
entirely.  Measured profiler aggregates ride along as ``seed`` metadata
so a stale cache (profile changed underneath it) can be detected and
re-tuned with ``force=True``.

Cache file (JSON, ``REPRO_AUTOTUNE_CACHE`` overrides the location)::

    {"version": 1,
     "entries": {
       "matmul|cpu|float32|page=-":      {"block_m": 256, "block_n": 256,
                                          "block_k": 256, "t_us": 812.4,
                                          "shape": [256, 768, 3072]},
       "quant_matmul8|cpu|int8|page=-":  {...},
       "paged_decode|cpu|float32|page=4": {"impl": "reference",
                                           "t_us": 95.1}}}

Selections are *applied* through ``kernels.ops.set_tuned`` — the jitted
wrappers resolve their default tiles from the applied entry (falling
back whenever a tuned tile does not divide the call's shape), and
``core.modules.resolve_attn_impl`` consults the applied paged-decode
impl when asked for ``"auto"``.
"""
from __future__ import annotations

import json
import os
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref

CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
VERSION = 1

# candidate tile edges (fitted to each shape's divisors before timing)
_BM_CANDIDATES = (64, 128, 256)
_BN_CANDIDATES = (64, 128, 256)
_BK_CANDIDATES = (128, 256, 512)


def default_cache_path() -> Path:
    env = os.environ.get(CACHE_ENV)
    if env:
        return Path(env)
    return Path.home() / ".cache" / "repro" / "autotune.json"


def device_arch() -> str:
    """Stable per-device key: the default device's ``device_kind``
    (``"tpu-v5-lite"`` on a v5e, ``"cpu"`` on the CPU backend).  A
    process that sees no device raises here rather than keying tuned
    tiles or profiles under a made-up name."""
    return jax.devices()[0].device_kind.strip().lower().replace(" ", "-")


class AutotuneCache:
    """Disk-backed map of ``(kernel, arch, dtype, page_size)`` -> choice."""

    def __init__(self, path=None):
        self.path = Path(path) if path is not None else default_cache_path()
        self.entries: Dict[str, dict] = {}
        if self.path.exists():
            try:
                blob = json.loads(self.path.read_text())
                if blob.get("version") == VERSION:
                    self.entries = dict(blob.get("entries", {}))
            except (OSError, ValueError):
                self.entries = {}

    @staticmethod
    def key(kernel: str, *, arch: str, dtype: str,
            page_size: Optional[int] = None) -> str:
        page = "-" if not page_size else str(int(page_size))
        return f"{kernel}|{arch}|{dtype}|page={page}"

    def get(self, kernel: str, *, arch: str, dtype: str,
            page_size: Optional[int] = None) -> Optional[dict]:
        return self.entries.get(self.key(kernel, arch=arch, dtype=dtype,
                                         page_size=page_size))

    def put(self, kernel: str, entry: dict, *, arch: str, dtype: str,
            page_size: Optional[int] = None):
        self.entries[self.key(kernel, arch=arch, dtype=dtype,
                              page_size=page_size)] = entry

    def save(self):
        self.path.parent.mkdir(parents=True, exist_ok=True)
        tmp = self.path.with_suffix(".tmp")
        tmp.write_text(json.dumps({"version": VERSION,
                                   "entries": self.entries}, indent=1))
        tmp.replace(self.path)


def _fit(block: int, dim: int) -> int:
    """Largest tile <= ``block`` that divides ``dim`` (the kernels
    require divisible tiling after clamping)."""
    b = min(block, dim)
    while dim % b:
        b -= 1
    return b


def _median_time(fn, reps: int = 3) -> float:
    fn()                                      # warmup / compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _tile_candidates(m: int, k: int, n: int,
                     bits: Optional[int]) -> List[Tuple[int, int, int]]:
    cands = []
    for bm in _BM_CANDIDATES:
        for bn in _BN_CANDIDATES:
            for bk in _BK_CANDIDATES:
                t = (_fit(bm, m), _fit(bn, n), _fit(bk, k))
                if bits == 4 and t[2] % 2:
                    continue              # int4 packs two rows per byte
                if t not in cands:
                    cands.append(t)
    return cands


def tune_matmul(m: int, k: int, n: int, *, dtype: str = "float32",
                bits: Optional[int] = None,
                cache: Optional[AutotuneCache] = None,
                arch: Optional[str] = None, reps: int = 3,
                force: bool = False) -> dict:
    """Search ``(block_m, block_n, block_k)`` for ``streamed_matmul``
    (``bits=None``) or ``quantized_matmul`` at the given shape; the
    winner is cached per ``(arch, dtype)`` so repeat runs skip the
    timing sweep."""
    cache = cache if cache is not None else AutotuneCache()
    arch = arch or device_arch()
    kernel = "matmul" if bits is None else f"quant_matmul{bits}"
    hit = cache.get(kernel, arch=arch, dtype=dtype)
    if hit is not None and not force:
        return hit
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    if bits is None:
        w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
        run = lambda t: ops.matmul(x, w, block_m=t[0], block_n=t[1],  # noqa: E731,E501
                                   block_k=t[2]).block_until_ready()
    else:
        iinfo_max = 127 if bits == 8 else 7
        kw = k if bits == 8 else k // 2
        w_q = jnp.asarray(rng.integers(-iinfo_max, iinfo_max, (kw, n)),
                          jnp.int8)
        scale = jnp.asarray(rng.uniform(0.5, 1.5, (n,)), jnp.float32)
        run = lambda t: ops.quant_matmul(x, w_q, scale, bits=bits,  # noqa: E731,E501
                                         block_m=t[0], block_n=t[1],
                                         block_k=t[2]).block_until_ready()
    best, best_t = None, float("inf")
    for tile in _tile_candidates(m, k, n, bits):
        dt = _median_time(lambda: run(tile), reps=reps)
        if dt < best_t:
            best, best_t = tile, dt
    entry = {"block_m": best[0], "block_n": best[1], "block_k": best[2],
             "t_us": best_t * 1e6, "shape": [m, k, n]}
    cache.put(kernel, entry, arch=arch, dtype=dtype)
    cache.save()
    return entry


def tune_paged_decode(page_size: int, *, dtype: str = "float32",
                      kv_heads: int = 2, groups: int = 2,
                      head_dim: int = 64, pages_per_row: int = 4,
                      cache: Optional[AutotuneCache] = None,
                      arch: Optional[str] = None, reps: int = 3,
                      force: bool = False) -> dict:
    """Pick the paged-decode implementation — the block-table Pallas
    kernel vs the jnp gather reference — for this device and page size
    (the page IS the kernel's tile, so the choice is page-size-keyed)."""
    cache = cache if cache is not None else AutotuneCache()
    arch = arch or device_arch()
    hit = cache.get("paged_decode", arch=arch, dtype=dtype,
                    page_size=page_size)
    if hit is not None and not force:
        return hit
    rng = np.random.default_rng(0)
    b = 2
    pool = pages_per_row * b
    q = jnp.asarray(rng.standard_normal((b, kv_heads, groups, head_dim)),
                    jnp.float32)
    kp = jnp.asarray(rng.standard_normal((pool, page_size, kv_heads,
                                          head_dim)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal(kp.shape), jnp.float32)
    tables = jnp.asarray(
        rng.permutation(pool).reshape(b, pages_per_row), jnp.int32)
    lengths = jnp.full((b,), pages_per_row * page_size - 1, jnp.int32)
    ref_fn = jax.jit(ref.paged_decode_ref)
    timings = {
        "pallas": _median_time(
            lambda: ops.paged_decode(q, kp, vp, tables,
                                     lengths).block_until_ready(),
            reps=reps),
        "reference": _median_time(
            lambda: ref_fn(q, kp, vp, tables,
                           lengths).block_until_ready(),
            reps=reps),
    }
    impl = min(timings, key=timings.get)
    entry = {"impl": impl, "t_us": timings[impl] * 1e6,
             "t_us_other": max(timings.values()) * 1e6}
    cache.put("paged_decode", entry, arch=arch, dtype=dtype,
              page_size=page_size)
    cache.save()
    return entry


def tune_for_model(cfg, profile: Optional[dict] = None, *,
                   page_size: Optional[int] = None,
                   quant: Optional[str] = None,
                   cache_path=None, tokens: int = 256,
                   reps: int = 3, force: bool = False,
                   apply: bool = True) -> dict:
    """Model-shaped autotune pass, seeded by the Layer Profiler.

    The matmul sweep runs at the model's FFN shape (``tokens x d_model @
    d_model x d_ff`` — the streaming hot spot); the profile supplies the
    shard dtype and its measured ``layer_t_comp`` / ``layer_t_load``
    aggregates, which are stored as ``seed`` metadata on the entries.
    Returns the selections and (``apply=True``) installs them as the
    jitted wrappers' default tiles via ``kernels.ops.set_tuned``.
    """
    cache = AutotuneCache(cache_path)
    dtype = (profile or {}).get("ckpt_dtype") or getattr(cfg, "dtype",
                                                        "float32")
    quant = quant or (profile or {}).get("quant")
    bits = {"int8": 8, "int4": 4}.get(quant or "")
    m = max(8, int(tokens))
    k = int(cfg.d_model)
    n = int(getattr(cfg, "d_ff", 4 * cfg.d_model))
    seed = None
    if profile:
        seed = {"layer_t_comp": profile.get("layer_t_comp"),
                "layer_t_load": profile.get("layer_t_load")}
    out = {"arch": device_arch(), "dtype": dtype}
    mat = tune_matmul(m, k, n, dtype=dtype, cache=cache, reps=reps,
                      force=force)
    if seed and "seed" not in mat:
        mat["seed"] = seed
        cache.save()
    out["matmul"] = mat
    if bits is not None:
        out["quant_matmul"] = tune_matmul(m, k, n, dtype=quant, bits=bits,
                                          cache=cache, reps=reps,
                                          force=force)
    if page_size:
        head_dim = int(getattr(cfg, "head_dim", 64))
        kv = int(getattr(cfg, "n_kv_heads", None)
                 or getattr(cfg, "n_heads", 2))
        g = max(1, int(getattr(cfg, "n_heads", kv)) // max(kv, 1))
        out["paged_decode"] = tune_paged_decode(
            int(page_size), dtype=dtype, kv_heads=kv, groups=g,
            head_dim=head_dim, cache=cache, reps=reps, force=force)
    if apply:
        apply_tuning(out)
    return out


def apply_tuning(selection: dict):
    """Install a ``tune_for_model`` selection as process-wide defaults
    for the jitted kernel wrappers (and the auto attn-impl choice)."""
    ops.set_tuned(matmul=selection.get("matmul"),
                  quant_matmul=selection.get("quant_matmul"),
                  paged_impl=(selection.get("paged_decode") or {})
                  .get("impl"))
