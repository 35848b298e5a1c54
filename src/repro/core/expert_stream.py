"""Expert-streaming PIPELOAD: routing-aware MoE shard loading (beyond-paper).

Hermes streams whole layers because a dense layer is all-or-nothing: every
weight participates in every token.  A mixture-of-experts FFN is not —
with ``top_k`` of ``n_experts`` experts active per token, only
``~top_k/n_experts`` of the FFN bytes matter for any given round (6% for
the 128-expert top-8 configs in the zoo).  This module exploits that
routing sparsity *losslessly*: the checkpoint is partitioned into
per-layer attention+router shards plus ONE SHARD PER EXPERT
(``checkpoint/partition.py`` with ``expert_split=True``), the Loading
Agents stream attention+router eagerly exactly as before, and the
experts are fetched on demand — after the router runs, the engine loads
only the union of top-k experts activated by the round's batch.

Two pieces live here:

  * ``ExpertCache`` — LRU residency of hot experts.  Routing is heavily
    reused across decode rounds (the same few experts keep winning), so
    caching fetched experts converts repeat activations into disk-free
    hits.  The cache's bytes are charged to the engine's ``_Ledger``:
    for budgeted runs the engine reserves the cache capacity up front —
    the same protocol KV pages use, because the Inference Agent is the
    thread that raises ``S_dest`` and must never park on ``S_stop``
    itself — and under admission pressure the scheduler shrinks the
    reservation (``release_headroom``), evicting LRU experts and
    releasing their ledger bytes through the same path a destroyed
    layer's bytes take, so blocked loaders and waiting requests wake.
    Unbudgeted runs charge per-expert acquire/release instead, so
    ``peak_bytes`` stays a faithful account.
  * ``ExpertStreamEngine`` — the demand-loading logic the engine's
    Inference Agent calls per MoE layer: run the jitted attention+router
    module, read the batch's top-k expert ids back to the host, fetch
    the union (cache hits skip the disk; misses load in parallel on a
    worker pool — the expert-side Loading Agents), then run the jitted
    combine module over the streamed per-expert weights.  Fetched
    expert sets are padded to power-of-two buckets so the combine
    executable compiles once per bucket, not once per union size.

Streamed-MoE outputs are bit-compatible with the in-memory
``models/moe.py`` oracle: the router, capacity-based dispatch and
combine math are the same functions, evaluated over only the experts
that received tokens (the dropped rows were all-zero anyway) — see
``core/modules.py``.
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.partition import load_shard
from repro.core import telemetry as _tele
from repro.core.prefetch import PrefetchRuntime
from repro.models.config import ModelConfig

_Key = Tuple[str, int]   # (layer shard name, expert index)


class ExpertCache:
    """LRU map of (layer, expert) -> device weights, with byte accounting.

    Pure residency bookkeeping — ledger interaction lives in
    ``ExpertStreamEngine`` so the cache itself is trivially unit-testable.
    """

    def __init__(self):
        self._entries: "OrderedDict[_Key, Tuple[dict, int]]" = OrderedDict()
        self.resident = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: _Key) -> bool:
        return key in self._entries

    def get(self, key: _Key) -> Optional[dict]:
        """Hit -> weights (entry becomes most-recently-used); miss -> None."""
        entry = self._entries.get(key)
        if entry is None:
            self.misses += 1
            return None
        self._entries.move_to_end(key)
        self.hits += 1
        return entry[0]

    def peek(self, key: _Key) -> Optional[dict]:
        """Lookup without touching the hit/miss counters or LRU order
        (the fetch path's duplicate re-check)."""
        entry = self._entries.get(key)
        return None if entry is None else entry[0]

    def put(self, key: _Key, weights: dict, nbytes: int):
        old = self._entries.get(key)
        if old is not None:
            # replacing an entry must not double-count its bytes
            self.resident -= old[1]
        self._entries[key] = (weights, int(nbytes))
        self._entries.move_to_end(key)
        self.resident += int(nbytes)

    def evict_lru(self, exclude: frozenset = frozenset()
                  ) -> Optional[Tuple[_Key, int]]:
        """Drop the least-recently-used entry not in ``exclude``;
        returns (key, freed bytes) or None if nothing is evictable."""
        for key in self._entries:
            if key not in exclude:
                _, nbytes = self._entries.pop(key)
                self.resident -= nbytes
                self.evictions += 1
                return key, nbytes
        return None


class ExpertStreamEngine:
    """Demand-loading of per-expert shards for one partitioned checkpoint.

    Owned by ``PipeloadEngine`` when the manifest says ``expert_split``;
    the engine's per-layer apply paths route MoE layers through
    ``layer`` / ``layer_cache`` / ``layer_decode`` here instead of the
    whole-layer module fns.
    """

    def __init__(self, ckpt_dir, manifest: dict, cfg: ModelConfig, fns,
                 *, workers: int = 4, cache_bytes: Optional[int] = None,
                 runtime: Optional[PrefetchRuntime] = None):
        self.dir = Path(ckpt_dir)
        self.cfg = cfg
        self.fns = fns
        by_index = {s["index"]: s["name"] for s in manifest["shards"]
                    if s["kind"] == "layer"}
        self.rows: Dict[str, Dict[int, dict]] = {}
        for s in manifest["shards"]:
            if s["kind"] != "expert":
                continue
            layer_name = by_index[s["index"]]
            self.rows.setdefault(layer_name, {})[s["expert"]] = s
        if not self.rows:
            raise ValueError(
                f"manifest at {self.dir} is tagged expert_split but holds "
                f"no kind='expert' shards")
        all_rows = [r for per in self.rows.values() for r in per.values()]
        self.total_bytes = int(sum(r["bytes"] for r in all_rows))
        self.max_expert_bytes = int(max(r["bytes"] for r in all_rows))
        # smallest cache that cannot wedge a single-token decode round:
        # one layer's top_k activated experts must be co-resident
        self.min_ws = self.working_set_bytes(1)
        self.cache = ExpertCache()
        self.reserved = 0            # the cache's byte allotment
        self._reserved_mode = False  # True = capacity charged up front
        self._ledger = None
        self._events: List = []
        self._t0 = 0.0
        self._lock = threading.Lock()
        # the expert-side Loading Agents ride the unified prefetch
        # runtime: the owning PipeloadEngine shares its pool; standalone
        # users (the profiler, unit tests) get a private one that
        # ``close()`` tears down (the old per-engine ThreadPoolExecutor
        # was never shut down and leaked its worker threads)
        self._owns_runtime = runtime is None
        self._runtime = runtime if runtime is not None else PrefetchRuntime(
            workers=max(1, workers), name="expert-loader")
        self._zero_expert = None     # padding template (per-family shapes)
        # O(1) round bookkeeping: counters + the current round's set only
        self._rounds = 0
        self._unique_total = 0
        self._round_seen: set = set()

    def close(self):
        """Join the fetch pool's worker threads (only if this engine owns
        the runtime — a shared pool belongs to the PipeloadEngine)."""
        if self._owns_runtime:
            self._runtime.close()

    def __enter__(self) -> "ExpertStreamEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def working_set_bytes(self, tokens: int) -> int:
        """Bytes of the widest single fetch a round with ``tokens`` batch
        tokens can lock: min(E, tokens * top_k) experts co-resident.
        The cache's allotment must never drop below the workload's
        working set, or a round wedges with everything locked."""
        u = min(self.cfg.n_experts, max(int(tokens), 1) * self.cfg.top_k)
        return u * self.max_expert_bytes

    # -- ledger binding ------------------------------------------------
    @property
    def bound(self) -> bool:
        return self._ledger is not None

    def bound_to(self, ledger) -> bool:
        return self._ledger is ledger

    def reserve(self, ledger, capacity: int, events, t0: float):
        """Bind this run's ledger and charge the cache's capacity to it
        (budgeted ledgers reserve up front — the KV-page protocol; see
        module docstring).  Re-binding the same ledger is a no-op, so a
        serving session reserves once."""
        if ledger is self._ledger:
            return
        capacity = int(min(capacity, self.total_bytes))
        with self._lock:
            # a tighter run than the last one: shed residency first (the
            # old ledger is gone with its run, nothing to release there)
            while self.cache.resident > capacity:
                self.cache.evict_lru()
            self._ledger = ledger
            self._events = events
            self._t0 = t0
            self._reserved_mode = ledger.budget is not None
            if self._reserved_mode:
                self.reserved = max(capacity, self.cache.resident)
                ledger.acquire(self.reserved, owner="expert_cache")
            else:
                self.reserved = capacity
                if self.cache.resident:
                    ledger.acquire(self.cache.resident, owner="expert_cache")
            events.append((time.perf_counter() - t0, "expert_reserve",
                           str(self.reserved)))

    def release_headroom(self, nbytes: int,
                         floor: Optional[int] = None) -> int:
        """Shrink the budgeted reservation by up to ``nbytes`` (never
        below ``floor`` — the caller's workload working set, defaulting
        to ``min_ws``), evicting LRU experts and releasing their ledger
        bytes — the cache-side ``S_dest`` path the scheduler's admission
        control pulls on when a queued request needs pages."""
        if not self._reserved_mode or self._ledger is None:
            return 0
        with self._lock:
            target = max(self.min_ws, floor or 0,
                         self.reserved - int(nbytes))
            if target >= self.reserved:
                return 0
            while self.cache.resident > target:
                evicted = self.cache.evict_lru()
                if evicted is None:
                    break
                self._event("expert_evict", f"{evicted[0][0]}#{evicted[0][1]}")
            target = max(target, self.cache.resident)
            freed = self.reserved - target
            self.reserved = target
        if freed:
            self._ledger.release(freed, owner="expert_cache")
        return freed

    def clear(self):
        """Drop every cached expert (releasing per-expert ledger charges
        when unreserved).  Standalone users with no byte cap — the
        profiler times layer after layer with a warm cache — call this
        between layers so residency stays one layer's union, not the
        model's whole expert pool."""
        with self._lock:
            while True:
                evicted = self.cache.evict_lru()
                if evicted is None:
                    return
                if self._ledger is not None and not self._reserved_mode:
                    self._ledger.release(evicted[1], owner="expert_cache")

    # -- round bookkeeping ---------------------------------------------
    def begin_round(self):
        self._rounds += 1
        self._round_seen = set()

    def _event(self, kind: str, payload: str):
        self._events.append((time.perf_counter() - self._t0, kind, payload))

    def snapshot(self) -> dict:
        c = self.cache
        return {"hits": c.hits, "misses": c.misses,
                "evictions": c.evictions, "rounds": self._rounds,
                "unique": self._unique_total}

    def stats_since(self, snap: dict) -> dict:
        """RunStats/ServeStats field values accumulated since ``snap``."""
        now = self.snapshot()
        rounds = max(now["rounds"] - snap["rounds"], 1)
        return {
            "expert_hits": now["hits"] - snap["hits"],
            "expert_misses": now["misses"] - snap["misses"],
            "expert_evictions": now["evictions"] - snap["evictions"],
            "expert_cache_bytes": (self.reserved if self._reserved_mode
                                   else self.cache.resident),
            "unique_experts_per_round":
                (now["unique"] - snap["unique"]) / rounds,
        }

    # -- demand loading -------------------------------------------------
    def _load_one(self, row: dict) -> dict:
        name = row["name"]
        t = time.perf_counter() - self._t0
        host = load_shard(self.dir, name, row)
        w = jax.tree.map(jnp.asarray, host)
        self._events.append((t, "load_start", name))
        self._event("load_end", name)
        return w

    def fetch(self, layer_name: str, ids: Sequence[int]) -> List[dict]:
        """Resolve the round's activated experts for one layer: cache
        hits skip the disk, misses stream in parallel on the worker
        pool.  Returns weight dicts aligned with ``ids``."""
        tr = _tele.get_tracer()
        rows = self.rows[layer_name]
        locked = frozenset((layer_name, int(e)) for e in ids)
        out: Dict[int, dict] = {}
        missing: List[int] = []
        with self._lock:
            for e in ids:
                w = self.cache.get((layer_name, e))
                if w is None:
                    missing.append(e)
                else:
                    out[e] = w
            if missing:
                need = sum(rows[e]["bytes"] for e in missing)
                self._make_room(need, locked)
        m = _tele.metrics()
        m.counter("expert.hits").inc(len(ids) - len(missing))
        m.counter("expert.misses").inc(len(missing))
        if missing and tr.enabled:
            with tr.span("expert_fetch", layer=layer_name,
                         misses=len(missing)):
                self._fetch_missing(layer_name, rows, missing, out)
        elif missing:
            self._fetch_missing(layer_name, rows, missing, out)
        if self._rounds:
            self._unique_total += len(locked - self._round_seen)
            self._round_seen |= locked
        return [out[int(e)] for e in ids]

    def _fetch_missing(self, layer_name: str, rows, missing: List[int],
                       out: Dict[int, dict]) -> None:
        if missing:
            futures = [(e, self._runtime.submit(self._load_one, rows[e]))
                       for e in missing]
            for e, fut in futures:
                w = fut.result()
                nbytes = rows[e]["bytes"]
                charge = (self._ledger is not None
                          and not self._reserved_mode)
                if charge:
                    # unreserved acquire never parks (no budget gate), so
                    # charging before the dup re-check below cannot wedge
                    self._ledger.acquire(nbytes, owner="expert_cache")
                with self._lock:
                    # re-check under the lock: a concurrent fetch that
                    # missed on the same (layer, expert) while we held no
                    # lock may have put it already — overwriting would
                    # strand its ledger charge (double-charge bug)
                    cached = self.cache.peek((layer_name, e))
                    duplicate = cached is not None
                    if duplicate:
                        out[e] = cached
                    else:
                        self.cache.put((layer_name, e), w, nbytes)
                        out[e] = w
                if duplicate and charge:
                    self._ledger.release(nbytes, owner="expert_cache")  # drop our copy's charge
                del w

    def _make_room(self, need: int, locked: frozenset):
        """Evict LRU entries until ``need`` more bytes fit the cache's
        allotment (``reserved`` — the up-front ledger reservation for
        budgeted runs, the engine-chosen capacity otherwise; an unbound
        engine — standalone use, e.g. the profiler — is uncapped)."""
        if self._ledger is None:
            return
        cap = self.reserved
        while self.cache.resident + need > cap:
            evicted = self.cache.evict_lru(exclude=locked)
            if evicted is None:
                locked_bytes = self.cache.resident
                raise ValueError(
                    f"expert cache too small for this round's working "
                    f"set on {next(iter(locked))[0]}: needs "
                    f"{locked_bytes + need} bytes co-resident but the "
                    f"cache reservation is {cap}; raise the budget / "
                    f"expert_cache_bytes, or let the generation-aware "
                    f"planner size the cache")
            key, nbytes = evicted
            if self._ledger is not None and not self._reserved_mode:
                self._ledger.release(nbytes, owner="expert_cache")
            self._event("expert_evict", f"{key[0]}#{key[1]}")
            _tele.metrics().counter("expert.evictions").inc()

    # -- union + padding -------------------------------------------------
    def _union(self, top_ids) -> List[int]:
        return [int(e) for e in np.unique(np.asarray(top_ids))]

    def _bucket(self, u: int) -> int:
        """Pad union sizes to powers of two (>= top_k) so the combine
        module compiles once per bucket instead of once per union size."""
        b = max(self.cfg.top_k, 1)
        while b < u:
            b *= 2
        return min(b, self.cfg.n_experts)   # the union never exceeds E

    def _gather(self, layer_name: str, ids: List[int]):
        ws = self.fetch(layer_name, ids)
        u = self._bucket(len(ids))
        if self._zero_expert is None:
            self._zero_expert = jax.tree.map(jnp.zeros_like, ws[0])
        experts = tuple(ws) + (self._zero_expert,) * (u - len(ids))
        sel = np.full((u,), -1, np.int32)
        sel[:len(ids)] = ids
        return experts, jnp.asarray(sel)

    # -- per-layer apply paths (Inference Agent steps) -------------------
    def layer(self, layer_name: str, weights, x):
        xa, hf, top_w, top_ids = self.fns["moe_router"](weights, x)
        experts, sel = self._gather(layer_name, self._union(top_ids))
        out = self.fns["moe_combine"](experts, sel, xa, hf, top_w, top_ids)
        out.block_until_ready()
        return out

    def layer_cache(self, layer_name: str, weights, x, total_len: int):
        xa, cache, hf, top_w, top_ids = self.fns["moe_router_cache"](
            weights, x, total_len)
        experts, sel = self._gather(layer_name, self._union(top_ids))
        out = self.fns["moe_combine"](experts, sel, xa, hf, top_w, top_ids)
        out.block_until_ready()
        return out, cache

    def layer_decode(self, layer_name: str, weights, x, cache, pos):
        xa, new_cache, hf, top_w, top_ids = self.fns["moe_router_decode"](
            weights, x, cache, pos)
        experts, sel = self._gather(layer_name, self._union(top_ids))
        out = self.fns["moe_combine"](experts, sel, xa, hf, top_w, top_ids)
        out.block_until_ready()
        return out, new_cache
