"""PIPELOAD Execution Engine (Hermes paper §III).

Three worker roles communicate through an explicit signalling mechanism:

  * ``m`` **Loading Agents** (threads): agent *i* loads shard stripe
    ``L_{i+jm}`` (paper's round-robin assignment) from the layer-partitioned
    on-disk checkpoint, then raises ``S_comp(k)`` (computation-ready).
  * one **Inference Agent** (caller thread): maintains the inference queue —
    layer *k* computes only after *k-1* — and raises ``S_dest(k)`` (memory
    destruction) as soon as layer *k*'s forward pass finishes.
  * one **Daemon Agent** (thread): maintains the resident-bytes ledger,
    frees destroyed layers, and enforces the memory budget: a loader asking
    to exceed the budget blocks (the paper's ``S_stop``) until the daemon
    frees enough space and wakes it.

Engine modes:
  * ``baseline``   — load the whole model, then infer (no pipeline).
  * ``pipeswitch`` — standard pipeline: ONE loading agent, no destruction
    (PipeSwitch-style; peak memory == whole model).
  * ``pipeload``   — the paper's mechanism with ``num_agents`` loaders.

``pin_window > 0`` implements the paper's future-work item (beyond-paper):
the first ``pin_window`` layers stay resident across GPT token iterations,
skipping their reload in later pipeline rounds while still honouring the
budget (the Pipeline Planner picks the window from the schedule).

Generation runs in one of two regimes:

  * ``run_generate(..., kv_cache=False)`` — the paper's engine: the full
    load+prefix pipeline re-runs for EVERY generated token (§V-B2).
  * ``run_generate(..., kv_cache=True)`` — beyond-paper incremental decode:
    ONE pipelined prefill captures a per-layer KV cache (charged to the
    ledger, so weights + cache share the budget), then each token is a
    single-token decode pass that still streams non-pinned layer weights
    through the Loading Agents but touches only O(1) new activations.

Multi-request serving amortises the weight stream further:
``run_batch_round`` runs ONE pipeline round whose Inference Agent step
applies each streamed layer to EVERY in-flight request (stacked decode
states with ragged positions + joining prefills) before destroying it —
the continuous-batching scheduler (core/scheduler.py) drives it.

Quantized checkpoints (int8/int4 shards, checkpoint/quant.py) flow
through unchanged: ``load_shard`` hands back ``QuantizedTensor`` leaves,
the manifest ``bytes`` every ledger acquire/release uses are the
*quantized* sizes (so ``S_stop`` gates, the KV decode floor and the
batch-round admission maths all shrink with the shards), and the module
fns dequantize in-jit at compute time.  The per-layer fp copy is a
transient XLA temporary — like activations, it is not a resident tier
the ledger tracks.

Expert-split MoE checkpoints (manifest ``expert_split``,
core/expert_stream.py) change WHAT a pipeline stage is, not how it
flows: the Loading Agents stripe the per-layer attention+router shards
exactly as above, and the Inference Agent's per-layer step becomes
router-then-demand-load — run the attention+router module, read back the
batch's top-k expert ids, fetch only that union (LRU ExpertCache hits
skip the disk; misses stream on a worker pool), then run the combine
module over the streamed experts.  The cache's capacity is reserved
through the ledger up front for budgeted runs (the KV-page protocol:
the Inference Agent raises ``S_dest`` and must never park on ``S_stop``
itself) and shrinks under admission pressure via LRU eviction.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import sys
import threading
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.partition import load_manifest, load_shard
from repro.core import telemetry as _tele
from repro.core.kv_pages import pages_for
from repro.core.modules import build_module_fns
from repro.core.prefetch import PrefetchRuntime
from repro.models.config import ModelConfig

MODES = ("baseline", "pipeswitch", "pipeload")


@dataclasses.dataclass
class RunStats:
    mode: str
    num_agents: int
    latency_s: float
    peak_bytes: int
    events: List[Tuple[float, str, str]]
    loads: int = 0
    streamed_bytes: int = 0   # disk bytes read (quantized shards shrink it)
    # generation extras (0 for single-pass runs)
    new_tokens: int = 0
    prefill_s: float = 0.0
    decode_s: float = 0.0
    cache_bytes: int = 0
    kv_cache: bool = False
    # expert-streaming extras (0 for dense / whole-layer MoE runs)
    expert_hits: int = 0
    expert_misses: int = 0
    expert_evictions: int = 0
    expert_cache_bytes: int = 0
    unique_experts_per_round: float = 0.0
    # speculative-decoding extras (0 for non-speculative runs)
    spec_depth: int = 0
    spec_rounds: int = 0           # draft-propose / verify rounds run
    draft_tokens: int = 0          # tokens the draft proposed
    accepted_tokens: int = 0       # proposals the target confirmed
    # prefetch fault-injection outcomes (REPRO_PREFETCH_FAULT_RATE),
    # wired from the telemetry metrics registry as per-run deltas
    retries: int = 0               # transient load failures retried
    faults_absorbed: int = 0       # injected faults hidden by retries
    # per-owner byte shares at the ledger peak (sums exactly to
    # peak_bytes; empty for runs that never charged the ledger)
    peak_breakdown: Dict[str, int] = dataclasses.field(default_factory=dict)

    def event_log(self, kinds=None):
        return [e for e in self.events if kinds is None or e[1] in kinds]

    @property
    def per_token_s(self) -> float:
        """Mean latency per generated token (whole run / tokens)."""
        return self.latency_s / self.new_tokens if self.new_tokens else 0.0

    @property
    def expert_hit_rate(self) -> float:
        """Fraction of expert activations served from the ExpertCache."""
        total = self.expert_hits + self.expert_misses
        return self.expert_hits / total if total else 0.0

    @property
    def acceptance_rate(self) -> float:
        """Fraction of draft proposals the target accepted."""
        return (self.accepted_tokens / self.draft_tokens
                if self.draft_tokens else 0.0)


#: Resident-tier owner taxonomy (docs/observability.md §Memory
#: attribution).  Every acquire/release names one of these (or a new
#: tag, which just works — the taxonomy is advisory, not an enum):
#:   pin            pinned window + embed/head aux + baseline weights
#:   stream         in-flight streamed layer shards (PrefetchStream)
#:   expert_cache   ExpertCache reservation / resident experts
#:   kv_pages       KV cache bytes, dense reservations and mapped pages
#:   draft          the pinned speculative-draft model's weights
#:   spec_headroom  per-request draft dense-cache rows
LEDGER_OWNERS = ("pin", "stream", "expert_cache", "kv_pages", "draft",
                 "spec_headroom")

_AUDIT_ENV = "REPRO_LEDGER_AUDIT"


class LedgerAuditError(AssertionError):
    """A memory-accounting invariant broke under ``REPRO_LEDGER_AUDIT=1``:
    a per-owner balance went negative (double release / wrong owner tag)
    or an owner held bytes at a drain point (leak).  The message names
    the owner and the call sites involved."""


def _caller_site(depth: int) -> str:
    """``file.py:line`` of the frame ``depth`` levels up (audit only —
    never runs on the un-audited hot path)."""
    try:
        f = sys._getframe(depth)
        return f"{Path(f.f_code.co_filename).name}:{f.f_lineno}"
    except Exception:  # pragma: no cover - interpreter without _getframe
        return "<unknown>"


class _LedgerAudit:
    """Event recorder behind a ``_Ledger`` when ``REPRO_LEDGER_AUDIT=1``.

    Keeps the full event log, a per-owner stack of outstanding acquires
    (with the acquiring call site), and per-``(owner, detail)`` balances
    so leaks can be pinned to a request id.  All methods are called with
    the ledger's cond lock held."""

    def __init__(self):
        # (op, owner, detail, nbytes, site) in program order
        self.events: List[Tuple[str, str, Optional[str], int, str]] = []
        # owner -> [(nbytes_outstanding, site), ...] LIFO
        self.open: Dict[str, List[Tuple[int, str]]] = {}
        # (owner, detail) -> outstanding bytes
        self.balance: Dict[Tuple[str, Optional[str]], int] = {}

    def charge(self, owner, detail, nbytes, depth=3):
        site = _caller_site(depth)
        self.events.append(("acquire", owner, detail, nbytes, site))
        self.open.setdefault(owner, []).append((nbytes, site))
        key = (owner, detail)
        self.balance[key] = self.balance.get(key, 0) + nbytes

    def credit(self, owner, detail, nbytes, owner_resident, depth=3):
        site = _caller_site(depth)
        self.events.append(("release", owner, detail, nbytes, site))
        if owner_resident < 0:
            stack = self.open.get(owner, [])
            last = stack[-1][1] if stack else "<no outstanding acquires>"
            raise LedgerAuditError(
                f"ledger audit: owner '{owner}' balance went negative "
                f"({owner_resident} bytes) releasing {nbytes} at {site} "
                f"— double release or wrong owner tag; last outstanding "
                f"acquire: {last}")
        key = (owner, detail)
        self.balance[key] = self.balance.get(key, 0) - nbytes
        # unwind the outstanding-acquire stack LIFO (releases may split
        # or merge acquires byte-wise; only the byte totals must match)
        left = nbytes
        stack = self.open.get(owner, [])
        while left > 0 and stack:
            got, site0 = stack.pop()
            if got > left:
                stack.append((got - left, site0))
                left = 0
            else:
                left -= got

    def move(self, src, dst, nbytes, src_resident, detail, depth=3):
        site = _caller_site(depth)
        self.events.append(("transfer", f"{src}->{dst}", detail, nbytes,
                            site))
        if src_resident < 0:
            raise LedgerAuditError(
                f"ledger audit: transfer of {nbytes} bytes from '{src}' "
                f"to '{dst}' at {site} drove '{src}' negative "
                f"({src_resident} bytes)")
        left = nbytes
        stack = self.open.get(src, [])
        while left > 0 and stack:
            got, site0 = stack.pop()
            if got > left:
                stack.append((got - left, site0))
                left = 0
            else:
                left -= got
        self.open.setdefault(dst, []).append((nbytes, site))

    def check_drained(self, by_owner, owners):
        bad = []
        for o in owners:
            resid = by_owner.get(o, 0)
            if resid:
                sites = [s for _, s in self.open.get(o, [])]
                where = ", ".join(sites[-3:]) if sites else "<unknown site>"
                bad.append(f"owner '{o}' holds {resid} bytes "
                           f"(outstanding acquires: {where})")
        if bad:
            raise LedgerAuditError(
                "ledger audit: non-zero residue at drain point: "
                + "; ".join(bad))


class _Ledger:
    """Resident-bytes accounting + budget gate (Daemon Agent state).

    Every ``acquire``/``release`` carries an ``owner`` tag (one of
    ``LEDGER_OWNERS``) so the scalar total decomposes into per-tier
    balances (``by_owner``); at every new peak the full breakdown is
    snapshotted under the same lock (``peak_breakdown``), so its values
    sum EXACTLY to ``peak``.  ``transfer`` re-attributes bytes between
    owners without touching the total (kept stream shards becoming
    pinned-window bytes).

    Telemetry: every acquire/release samples the resident total into the
    ``ledger.resident_bytes`` gauge plus a per-owner
    ``ledger.<owner>.resident_bytes`` gauge (always on — a few attribute
    stores) and, when tracing is enabled, into the
    ``ledger_resident_bytes`` / ``ledger_resident_bytes.<owner>``
    counter tracks the Chrome-trace exporter renders as residency
    timelines.  The traced sites guard on ``tracer.enabled`` so the
    disabled path adds no allocation.

    Audit mode (``REPRO_LEDGER_AUDIT=1``, default-on under pytest via
    tests/conftest.py) records every event with its call site and raises
    ``LedgerAuditError`` on negative per-owner balances (double release)
    or on residue at ``audit_check_drained`` points; off, the hot path
    pays only the ``by_owner`` dict update."""

    def __init__(self, budget: Optional[int]):
        self.budget = budget
        self.resident = 0
        self.peak = 0
        self.by_owner: Dict[str, int] = {}
        self.peak_breakdown: Dict[str, int] = {}
        self.cond = threading.Condition()
        self._gauge = _tele.metrics().gauge("ledger.resident_bytes")
        self._owner_gauges: Dict[str, object] = {}
        self.audit = (_LedgerAudit()
                      if os.environ.get(_AUDIT_ENV) == "1" else None)

    def _sample(self, owner: str):
        self._gauge.set(self.resident)
        og = self._owner_gauges.get(owner)
        if og is None:
            og = self._owner_gauges[owner] = _tele.metrics().gauge(
                f"ledger.{owner}.resident_bytes")
        og.set(self.by_owner.get(owner, 0))
        tr = _tele.get_tracer()
        if tr.enabled:
            tr.counter("ledger_resident_bytes", self.resident)
            tr.counter(f"ledger_resident_bytes.{owner}",
                       self.by_owner.get(owner, 0))

    def acquire(self, nbytes: int, stop_flag=None, *,
                owner: str = "untagged", detail: Optional[str] = None):
        """Loader-side: blocks while the budget would be exceeded
        (paper's S_stop semantics).  ``owner`` attributes the bytes to a
        resident tier; ``detail`` is an audit-only sub-key (request id,
        shard name) for per-entity residue queries."""
        with self.cond:
            if self.budget is not None:
                while (self.resident + nbytes > self.budget
                       and self.resident > 0
                       and not (stop_flag() if stop_flag else False)):
                    self.cond.wait(timeout=0.1)
            self.resident += nbytes
            self.by_owner[owner] = self.by_owner.get(owner, 0) + nbytes
            if self.resident > self.peak:
                self.peak = self.resident
                self.peak_breakdown = {o: b for o, b in
                                       self.by_owner.items() if b}
            if self.audit is not None:
                self.audit.charge(owner, detail, nbytes)
            self._sample(owner)

    def release(self, nbytes: int, *, owner: str = "untagged",
                detail: Optional[str] = None):
        with self.cond:
            self.resident -= nbytes
            self.by_owner[owner] = self.by_owner.get(owner, 0) - nbytes
            if self.audit is not None:
                self.audit.credit(owner, detail, nbytes,
                                  self.by_owner[owner])
            self._sample(owner)
            self.cond.notify_all()

    def transfer(self, nbytes: int, src: str, dst: str, *,
                 detail: Optional[str] = None):
        """Re-attribute ``nbytes`` resident bytes from owner ``src`` to
        ``dst`` (total resident unchanged — no budget interaction)."""
        with self.cond:
            self.by_owner[src] = self.by_owner.get(src, 0) - nbytes
            self.by_owner[dst] = self.by_owner.get(dst, 0) + nbytes
            if self.audit is not None:
                self.audit.move(src, dst, nbytes, self.by_owner[src],
                                detail)
            self._sample(src)
            self._sample(dst)

    def audit_check_drained(self, *owners: str):
        """Raise ``LedgerAuditError`` if any named owner still holds
        bytes.  No-op when audit mode is off, so drain points call it
        unconditionally."""
        if self.audit is None:
            return
        with self.cond:
            self.audit.check_drained(self.by_owner, owners)

    def audit_residue(self, owner: str, detail: Optional[str] = None):
        """Outstanding bytes for ``(owner, detail)`` — audit mode only
        (returns None when off)."""
        if self.audit is None:
            return None
        with self.cond:
            return self.audit.balance.get((owner, detail), 0)


def _fault_snap() -> Tuple[int, ...]:
    """Baseline of the prefetch fault counters (registry values)."""
    return _tele.counter_values("prefetch.retries",
                                "prefetch.faults_absorbed")


def _fault_delta(snap: Tuple[int, ...]) -> dict:
    """RunStats kwargs for faults absorbed since ``snap``."""
    now = _tele.counter_values("prefetch.retries",
                               "prefetch.faults_absorbed")
    return {"retries": now[0] - snap[0],
            "faults_absorbed": now[1] - snap[1]}


class DraftModel:
    """A small model pinned WHOLE for speculative drafting.

    Unlike the target — whose layers stream through the Loading Agents —
    the draft is tiny enough to live resident under the budget, like the
    pin window: ``pin`` loads every shard once and charges the ledger;
    proposals are then plain jitted calls with no disk traffic.  The
    draft keeps an ordinary dense KV cache (one contiguous
    ``total_len`` block, charged as extra resident bytes) because its
    cache is orders of magnitude smaller than the target's.
    """

    def __init__(self, ckpt_dir, cfg: ModelConfig, *,
                 attn_impl: Optional[str] = "auto"):
        self.dir = Path(ckpt_dir)
        self.cfg = cfg
        self.manifest = load_manifest(ckpt_dir)
        self.fns = build_module_fns(cfg, attn_impl=attn_impl)
        self.shards = {s["name"]: s for s in self.manifest["shards"]}
        if self.manifest.get("expert_split"):
            raise ValueError("expert-split checkpoints cannot be draft "
                             "models (the draft must pin whole)")
        self.layer_names = [s["name"] for s in self.manifest["shards"]
                            if s["kind"] == "layer"]
        self.total_bytes = sum(s["bytes"] for s in self.shards.values())
        self.weights: Optional[Dict[str, dict]] = None

    def cache_bytes(self, batch: int, total_len: int) -> int:
        return (len(self.layer_names)
                * self.cfg.cache_bytes(batch, total_len))

    def pin(self, ledger: Optional[_Ledger] = None):
        """Load every shard resident; charges ``ledger`` for the lot."""
        if ledger is not None:
            ledger.acquire(self.total_bytes, owner="draft")
        if self.weights is None:
            self.weights = {
                name: jax.tree.map(jnp.asarray,
                                   load_shard(self.dir, name, row))
                for name, row in self.shards.items()}
        return self

    def unpin(self, ledger: Optional[_Ledger] = None):
        """Return the draft's bytes to the budget (weights stay cached
        host-side for the next run; the LEDGER charge is what budgets)."""
        if ledger is not None:
            ledger.release(self.total_bytes, owner="draft")

    def prefill(self, tokens, total_len: int):
        """Prompt pass; returns (last-token logits (B, V), caches)."""
        assert self.weights is not None, "pin() the draft first"
        fns, w = self.fns, self.weights
        x = fns["embed"](w["embed"], jnp.asarray(tokens))
        caches: Dict[str, dict] = {}
        for name in self.layer_names:
            x, caches[name] = fns["layer_cache"](w[name], x, total_len)
        return fns["head"](w["head"], x), caches

    def decode(self, token: int, caches, pos: int):
        """Feed ``token`` at cache slot ``pos``; returns (logits (1, V),
        caches) — the draft's prediction for slot ``pos + 1``."""
        fns, w = self.fns, self.weights
        x = fns["embed"](w["embed"], jnp.full((1, 1), token, jnp.int32))
        for name in self.layer_names:
            x, caches[name] = fns["layer_decode"](
                w[name], x, caches[name], jnp.int32(pos))
        return fns["head"](w["head"], x), caches

    def decode_batch(self, tokens, caches, pos):
        """Stacked draft step for the serving scheduler: ``tokens``
        (R, 1) fed at ragged per-row slots ``pos`` (R,); returns
        (logits (R, V), caches with leading row dim R)."""
        fns, w = self.fns, self.weights
        x = fns["embed"](w["embed"], jnp.asarray(tokens, jnp.int32))
        pos = jnp.asarray(pos, jnp.int32)
        for name in self.layer_names:
            x, caches[name] = fns["layer_decode"](
                w[name], x, caches[name], pos)
        return fns["head"](w["head"], x), caches


@dataclasses.dataclass
class SpecConfig:
    """Speculative-decoding knobs for ``run_generate(speculative=...)``.

    ``depth`` draft tokens are proposed per round (the planner's
    ``spec_depth``); the draft checkpoint must fit resident next to the
    pinned window — the budget check charges it as extra resident
    bytes."""
    draft_dir: str
    draft_cfg: ModelConfig
    depth: int = 4


class PipeloadEngine:
    def __init__(self, ckpt_dir, cfg: ModelConfig, *,
                 mode: str = "pipeload", num_agents: int = 4,
                 budget_bytes: Optional[int] = None, pin_window: int = 0,
                 attn_impl: Optional[str] = "auto",
                 expert_cache_bytes: Optional[int] = None,
                 page_size: Optional[int] = None):
        assert mode in MODES, mode
        self.dir = Path(ckpt_dir)
        self.cfg = cfg
        self.mode = mode
        self.m = max(1, num_agents) if mode == "pipeload" else 1
        self.budget = budget_bytes
        self.pin = pin_window if mode == "pipeload" else 0
        # paged KV (core/kv_pages.py): cache ledger bytes are charged in
        # page_size-token pages as positions are reached, instead of one
        # max-length reservation up front.  None = dense reservation.
        self.page_size = page_size if page_size and page_size > 0 else None
        self.manifest = load_manifest(ckpt_dir)
        self.fns = build_module_fns(cfg, attn_impl=attn_impl)
        self.shards = {s["name"]: s for s in self.manifest["shards"]}
        self.layer_names = [s["name"] for s in self.manifest["shards"]
                            if s["kind"] == "layer"]
        # every shard read (always on; the read rate is
        # shard_read_bytes / shard_read_s)
        self._read_bytes = _tele.metrics().counter("loader.shard_read_bytes")
        self._read_s = _tele.metrics().counter("loader.shard_read_s")
        # persistent across pipeline rounds (pinning / non-destroying modes)
        self._resident: Dict[str, dict] = {}
        # the prefetch stream of the round in progress (telemetry only)
        self._stream_id: Optional[int] = None
        # ONE async prefetch runtime for every byte mover: the PIPELOAD
        # Loading Agents stream shard rounds through it and the expert
        # engine demand-loads on the same pool (core/prefetch.py)
        self.runtime = PrefetchRuntime(workers=self.m, name="pipeload")
        # expert-split MoE checkpoints demand-load experts post-router
        self.expert = None
        self.expert_cache_bytes = expert_cache_bytes
        if self.manifest.get("expert_split"):
            from repro.core.expert_stream import ExpertStreamEngine
            self.expert = ExpertStreamEngine(
                self.dir, self.manifest, cfg, self.fns, workers=self.m,
                cache_bytes=expert_cache_bytes, runtime=self.runtime)

    def close(self):
        """Tear down the prefetch runtime (joins worker + drainer
        threads).  Idempotent; the engine stays usable for module-level
        math but cannot run further pipeline rounds."""
        if self.expert is not None:
            self.expert.close()
        self.runtime.close()

    def __enter__(self) -> "PipeloadEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------------------------------------------------------
    def warmup(self, batch: int, seq: int, *, decode: bool = False,
               total_len: Optional[int] = None):
        """Compile the module fns ahead of the timed run (serving systems
        warm their executables; without this the first layer's jit compile
        stalls the Inference Agent while Loading Agents race ahead and the
        measured peak degenerates to the whole model).  ``decode=True``
        additionally compiles the KV-cache prefill/decode modules for the
        (batch, seq -> total_len) generation shape."""
        tokens = jnp.zeros((batch, seq), jnp.int32)
        emb = self._resident.get("embed") or self._load("embed")
        head = self._resident.get("head") or self._load("head")
        w0 = self._load(self.layer_names[0])
        x = self.fns["embed"](emb, tokens)
        if decode:
            total = total_len or (seq + 1)
            self.fns["embed"](emb, tokens[:, -1:])   # single-token shape
            _, cache = self._layer_cache(0, w0, x, total)
            x1, _ = self._layer_decode(0, w0, x[:, -1:], cache, seq)
            self.fns["head"](head, x1).block_until_ready()
        x = self._apply_layer(w0, x, k=0)
        self.fns["head"](head, x).block_until_ready()
        del w0, emb, head
        return self

    # ------------------------------------------------------------------
    def _load(self, name: str) -> dict:
        """Disk -> host -> device ("memory" tier).  Traced as
        ``shard_read`` (the raw read into one host buffer) and
        ``shard_put`` (issuing the host-to-device copies, which may
        still be in flight when it ends), with the round's stream number
        when a round's Loading Agent makes the load.  Traced or not, the
        read's manifest bytes and seconds add to the ``loader.*``
        counters."""
        row = self.shards[name]
        tr = _tele.get_tracer()
        if not tr.enabled:
            return jax.tree.map(jnp.asarray, self._read(row))
        args = {"key": name, "bytes": int(row["bytes"])}
        if self._stream_id is not None:
            args["stream"] = self._stream_id
        with tr.span("shard_read", **args):
            host = self._read(row)
        with tr.span("shard_put", **args):
            return jax.tree.map(jnp.asarray, host)

    def _read(self, row: dict) -> dict:
        t0 = time.perf_counter()
        host = load_shard(self.dir, row["name"], row)
        self._read_s.inc(time.perf_counter() - t0)
        self._read_bytes.inc(int(row["bytes"]))
        return host

    @contextlib.contextmanager
    def _round(self):
        """Holds the number the round's prefetch stream takes (made next,
        by this thread) until the stream has closed."""
        self._stream_id = self.runtime.streams
        try:
            yield self._stream_id
        finally:
            self._stream_id = None

    # Inference-agent steps of one layer, traced as ``layer_dispatch``
    # (the jitted call returns once its work is enqueued) and
    # ``layer_sync`` (the wait for that work to finish)
    def _layer_args(self, k: int) -> dict:
        args = {"layer": self.layer_names[k]}
        if self._stream_id is not None:
            args["stream"] = self._stream_id
        return args

    def _dispatch(self, k: int, fn: Callable, *args):
        tr = _tele.get_tracer()
        if not tr.enabled:
            return fn(*args)
        with tr.span("layer_dispatch", **self._layer_args(k)):
            return fn(*args)

    def _sync(self, k: int, x) -> None:
        tr = _tele.get_tracer()
        if not tr.enabled:
            x.block_until_ready()
            return
        with tr.span("layer_sync", **self._layer_args(k)):
            x.block_until_ready()

    # Per-layer apply paths.  Expert-split MoE checkpoints route through
    # the ExpertStreamEngine (router -> demand-load union -> combine);
    # everything else runs the whole-layer jitted module fns.
    def _apply_layer(self, weights, x, k: int = 0):
        if self.expert is not None:
            return self.expert.layer(self.layer_names[k], weights, x)
        y = self._dispatch(k, self.fns["layer"], weights, x)
        self._sync(k, y)
        return y

    def _layer_cache(self, k: int, weights, x, total_len: int):
        if self.expert is not None:
            return self.expert.layer_cache(self.layer_names[k], weights, x,
                                           total_len)
        return self.fns["layer_cache"](weights, x, total_len)

    def _layer_decode(self, k: int, weights, x, cache, pos):
        if self.expert is not None:
            return self.expert.layer_decode(self.layer_names[k], weights, x,
                                            cache, pos)
        return self.fns["layer_decode"](weights, x, cache, pos)

    def _streamed(self, events) -> int:
        """Total shard bytes read from disk this run (manifest sizes, so
        quantized checkpoints stream ~4x/8x fewer bytes per load)."""
        return sum(self.shards[e[2]]["bytes"] for e in events
                   if e[1] == "load_end")

    # ------------------------------------------------------------------
    def _run_pipeline(self, x, ledger: _Ledger, events, t0,
                      destroy: bool,
                      apply_fn: Optional[Callable] = None) -> jnp.ndarray:
        """One pipelined pass over the layer stack (PIPELOAD §III-B).

        ``apply_fn(k, weights, x) -> x`` is the Inference Agent's per-layer
        step; the default is the full-sequence forward.  The KV decode path
        substitutes a cache-aware closure — the loading/destruction
        machinery (S_comp / S_dest / S_stop) is identical.
        """
        names = self.layer_names
        n = len(names)
        if self.expert is not None:
            self.expert.begin_round()
        if apply_fn is None:
            apply_fn = lambda k, w, h: self._apply_layer(w, h, k=k)  # noqa: E731,E501

        # One prefetch stream per round (core/prefetch.py): the Loading
        # Agents are the runtime's pool workers, the Daemon Agent is its
        # destroy drainer, and the in-order grant discipline lives there
        # as a runtime policy.  Pinned layers (beyond-paper resident
        # window) ride along uncharged as ``preloaded`` entries.
        preloaded = {k: self._resident[names[k]] for k in range(n)
                     if names[k] in self._resident}
        tr = _tele.get_tracer()
        with self._round() as sid, self.runtime.stream(
                names, [self.shards[nm]["bytes"] for nm in names],
                self._load, ledger=ledger, preloaded=preloaded,
                events=events, t0=t0) as stream, \
                tr.span("stream_round", layers=n, stream=sid):
            # ---- Inference Agent (this thread): in-order inference queue
            for k in range(n):
                if tr.enabled:
                    with tr.span("shard_wait", layer=names[k], stream=sid):
                        w = stream.wait(k)           # S_comp(k)
                else:
                    w = stream.wait(k)               # S_comp(k)
                t = time.perf_counter()
                if tr.enabled:
                    with tr.span("compute", layer=names[k], stream=sid):
                        x = apply_fn(k, w, x)
                else:
                    x = apply_fn(k, w, x)
                events.append((t - t0, "comp_start", names[k]))
                events.append((time.perf_counter() - t0, "comp_end",
                               names[k]))
                name = names[k]
                pinned = k < self.pin
                if pinned and name not in self._resident:
                    self._resident[name] = w
                if destroy and not pinned:
                    stream.destroy(k, w)             # S_dest(k)
                else:
                    # pin window / pipeswitch: the weights and their
                    # ledger charge leave the stream with us — pinned
                    # layers re-attribute to the pin window, pipeswitch
                    # keeps stay stream bytes until the end-of-pass swap
                    stream.keep(k, owner="pin" if pinned else None)
                del w
        if not destroy:
            # pipeswitch: the whole model was resident for the pass (peak ==
            # full model); it is swapped out when the pass ends (PipeSwitch
            # time-shares the device between tasks), so the ledger releases
            # every non-pinned layer here.
            for k in range(n):
                if names[k] not in self._resident:
                    ledger.release(self.shards[names[k]]["bytes"],
                                   owner="stream")
        return x

    # ------------------------------------------------------------------
    def _ensure_aux(self, ledger: _Ledger, events, t0):
        """embed + head are the paper's "other layers": loaded up front,
        resident for the whole run."""
        for aux in ("embed", "head"):
            if aux not in self._resident:
                ledger.acquire(self.shards[aux]["bytes"],
                               owner="pin", detail=aux)
                self._resident[aux] = self._load(aux)
                events.append((time.perf_counter() - t0, "load_end", aux))

    def _bind_expert(self, ledger: _Ledger, events, t0, *,
                     round_tokens: int = 1):
        """Reserve the ExpertCache's capacity on this run's ledger (no-op
        when already bound to it).  Called after the run's fixed
        reservations (aux shards, KV pages) so the auto capacity is the
        budget headroom left once the pinned window and one streaming
        layer are spoken for.  ``round_tokens`` is the widest batch this
        run's rounds feed the router (a prefill's batch*seq); the cache
        must fit that round's expert working set, or the run would wedge
        mid-pipeline with every fetched expert locked."""
        if self.expert is None or self.expert.bound_to(ledger):
            return
        cap = self.expert_cache_bytes
        need = self.expert.working_set_bytes(round_tokens)
        if self.budget is not None:
            sizes = [self.shards[nm]["bytes"] for nm in self.layer_names]
            pinned = sum(sizes[:self.pin])
            streaming = max(sizes[self.pin:], default=0)
            head = self.budget - ledger.resident - pinned - streaming
            if cap is None:
                cap = head
            elif min(cap, self.expert.total_bytes) > head:
                # reserving past the headroom would park the Inference
                # Agent on S_stop forever — fail loudly instead
                raise ValueError(
                    f"expert_cache_bytes={cap} does not fit budget "
                    f"{self.budget}: only {head} bytes of headroom remain "
                    f"after other shards, KV pages, the pinned window and "
                    f"one streaming layer")
        elif cap is None:
            cap = self.expert.total_bytes
        if min(cap, self.expert.total_bytes) < need:
            raise ValueError(
                f"expert cache too small for this workload: "
                f"{min(cap, self.expert.total_bytes)} bytes available but "
                f"a {round_tokens}-token round can lock "
                f"{need} bytes of experts (min(n_experts, tokens*top_k) "
                f"co-resident); raise the budget / expert_cache_bytes, or "
                f"let the generation-aware planner size the schedule")
        self.expert.reserve(ledger, cap, events, t0)

    def _forward_once(self, tokens, ledger, events, t0) -> jnp.ndarray:
        """embed -> pipelined layers -> head."""
        self._ensure_aux(ledger, events, t0)
        self._bind_expert(ledger, events, t0,
                          round_tokens=tokens.shape[0] * tokens.shape[1])
        x = self.fns["embed"](self._resident["embed"], tokens)

        if self.mode == "baseline":
            # load-all-then-infer
            if self.expert is not None:
                self.expert.begin_round()
            weights = {}
            for name in self.layer_names:
                ledger.acquire(self.shards[name]["bytes"],
                               owner="pin", detail=name)
                weights[name] = self._load(name)
                events.append((time.perf_counter() - t0, "load_end", name))
            for k, name in enumerate(self.layer_names):
                x = self._apply_layer(weights[name], x, k=k)
            self._baseline_weights = weights     # resident (no destruction)
        else:
            destroy = self.mode == "pipeload"
            x = self._run_pipeline(x, ledger, events, t0, destroy)

        return self.fns["head"](self._resident["head"], x)

    # ------------------------------------------------------------------
    def _expert_snap(self) -> Optional[dict]:
        return self.expert.snapshot() if self.expert is not None else None

    def _expert_stats(self, snap: Optional[dict]) -> dict:
        """RunStats expert-streaming fields accumulated since ``snap``."""
        if self.expert is None:
            return {}
        return self.expert.stats_since(snap)

    def run_single(self, tokens) -> Tuple[jnp.ndarray, RunStats]:
        """Single-pass inference (BERT / ViT workloads)."""
        events: List[Tuple[float, str, str]] = []
        ledger = _Ledger(self.budget)
        snap = self._expert_snap()
        fsnap = _fault_snap()
        t0 = time.perf_counter()
        logits = self._forward_once(jnp.asarray(tokens), ledger, events, t0)
        logits.block_until_ready()
        lat = time.perf_counter() - t0
        return logits, RunStats(self.mode, self.m, lat, ledger.peak, events,
                                loads=sum(1 for e in events
                                          if e[1] == "load_end"),
                                streamed_bytes=self._streamed(events),
                                peak_breakdown=dict(ledger.peak_breakdown),
                                **self._expert_stats(snap),
                                **_fault_delta(fsnap))

    def run_generate(self, tokens, new_tokens: int, *,
                     kv_cache: bool = False,
                     speculative: Optional[SpecConfig] = None
                     ) -> Tuple[jnp.ndarray, RunStats]:
        """GPT-style generation.

        ``kv_cache=False`` reproduces the paper's engine: re-run the full
        load+prefix pipeline for EVERY generated token (§V-B2).
        ``kv_cache=True`` prefills once, then decodes token-by-token against
        per-layer KV caches (see module docstring).
        ``speculative`` (a ``SpecConfig``; requires ``page_size``) runs
        the draft/verify loop: a pinned draft proposes ``depth`` tokens
        per round and ONE stacked weight-stream round verifies them all
        — greedy-token-identical to the non-speculative paths."""
        if speculative is not None:
            return self._generate_spec(tokens, new_tokens, speculative)
        if kv_cache:
            return self._generate_kv(tokens, new_tokens)
        events: List[Tuple[float, str, str]] = []
        ledger = _Ledger(self.budget)
        snap = self._expert_snap()
        fsnap = _fault_snap()
        toks = jnp.asarray(tokens)
        t0 = time.perf_counter()
        prefill_s = 0.0
        if self.expert is not None:
            # re-prefill rounds GROW with every generated token; bind the
            # expert cache against the widest (last) round up front so an
            # infeasible budget fails here, not mid-generation
            b, s0 = toks.shape
            self._ensure_aux(ledger, events, t0)
            self._bind_expert(ledger, events, t0,
                              round_tokens=b * (s0 + new_tokens - 1))
        for step in range(new_tokens):
            if self.mode == "baseline" and step > 0:
                # baseline keeps the model resident: only re-infer
                if self.expert is not None:
                    self.expert.begin_round()
                x = self.fns["embed"](self._resident["embed"], toks)
                for k, name in enumerate(self.layer_names):
                    x = self._apply_layer(self._baseline_weights[name], x,
                                          k=k)
                logits = self.fns["head"](self._resident["head"], x)
            else:
                logits = self._forward_once(toks, ledger, events, t0)
            nxt = jnp.argmax(logits, -1).astype(toks.dtype)[:, None]
            toks = jnp.concatenate([toks, nxt], axis=1)
            if step == 0:
                nxt.block_until_ready()
                prefill_s = time.perf_counter() - t0
        toks.block_until_ready()
        lat = time.perf_counter() - t0
        return toks, RunStats(self.mode, self.m, lat, ledger.peak, events,
                              loads=sum(1 for e in events
                                        if e[1] == "load_end"),
                              streamed_bytes=self._streamed(events),
                              new_tokens=new_tokens, prefill_s=prefill_s,
                              decode_s=lat - prefill_s,
                              peak_breakdown=dict(ledger.peak_breakdown),
                              **self._expert_stats(snap),
                              **_fault_delta(fsnap))

    # ------------------------------------------------------------------
    def _generate_kv(self, tokens, new_tokens: int
                     ) -> Tuple[jnp.ndarray, RunStats]:
        """Incremental decode: one cache-capturing prefill, then
        ``new_tokens - 1`` single-token passes over the same pipeline."""
        if new_tokens <= 0:   # match the kv_cache=False path: no-op run
            return jnp.asarray(tokens), RunStats(self.mode, self.m, 0.0, 0,
                                                 [], kv_cache=True)
        events: List[Tuple[float, str, str]] = []
        ledger = _Ledger(self.budget)
        snap = self._expert_snap()
        fsnap = _fault_snap()
        toks = jnp.asarray(tokens)
        b, s0 = toks.shape
        total = s0 + new_tokens
        names = self.layer_names
        n = len(names)
        expert_floor = (self.expert.working_set_bytes(b * s0)
                        if self.expert is not None else None)
        # Paged accounting (core/kv_pages.py): charge the ledger one
        # page at a time as decode reaches new positions, instead of
        # the whole max-length block up front — the ledger peak tracks
        # pages actually mapped.  Feasibility still checks the final
        # page count (a single request cannot be preempted
        # mid-generation; the scheduler path can).  Expert-split MoE
        # keeps the dense up-front reservation: _bind_expert sizes the
        # ExpertCache from the ledger headroom at bind time, so decode
        # pages mapped LATER would find their bytes already handed to
        # the cache and park ensure_slots on S_stop forever.
        paged = bool(self.page_size) and self.expert is None
        if paged:
            ps = self.page_size
            cache_total = (pages_for(total, ps)
                           * n * self.cfg.cache_bytes(b, ps))
        else:
            cache_total = n * self.cfg.cache_bytes(b, total)
        self._check_kv_budget(cache_total, expert_floor=expert_floor)

        caches: Dict[str, dict] = {}
        t0 = time.perf_counter()
        self._ensure_aux(ledger, events, t0)
        # Reserve the cache bytes the NEXT round needs before its
        # pipeline starts: the Inference Agent raises S_dest, so letting
        # it block on S_stop mid-pipeline would deadlock; the floor
        # check above guarantees these boundary acquires never wait, and
        # loaders then see the correct streaming headroom each round.
        # Dense reservations grab everything here; paged runs grow
        # page-by-page via ensure_slots().
        mapped = {"bytes": 0}

        def ensure_slots(slots: int):
            """Grow the charged reservation to cover ``slots`` cache
            positions (rounded up to pages when paged)."""
            if paged:
                need = (pages_for(slots, self.page_size)
                        * n * self.cfg.cache_bytes(b, self.page_size))
            else:
                need = cache_total
            if need > mapped["bytes"]:
                ledger.acquire(need - mapped["bytes"], owner="kv_pages")
                events.append((time.perf_counter() - t0, "cache_reserve",
                               str(need - mapped["bytes"])))
                mapped["bytes"] = need

        ensure_slots(s0 if paged else total)
        self._bind_expert(ledger, events, t0, round_tokens=b * s0)
        x = self.fns["embed"](self._resident["embed"], toks)

        # ---- prefill: pipelined pass that also captures per-layer caches
        def prefill_apply(k, w, h):
            h, cache = self._layer_cache(k, w, h, total)
            h.block_until_ready()
            caches[names[k]] = cache
            events.append((time.perf_counter() - t0, "cache_alloc",
                           names[k]))
            return h

        if self.mode == "baseline":
            if self.expert is not None:
                self.expert.begin_round()
            weights = getattr(self, "_baseline_weights", None)
            if weights is None:
                weights = {}
                for name in names:
                    ledger.acquire(self.shards[name]["bytes"],
                                   owner="pin", detail=name)
                    weights[name] = self._load(name)
                    events.append((time.perf_counter() - t0, "load_end",
                                   name))
                self._baseline_weights = weights
            else:
                for name in names:   # already resident from an earlier run
                    ledger.acquire(self.shards[name]["bytes"],
                                   owner="pin", detail=name)
            for k, name in enumerate(names):
                x = prefill_apply(k, weights[name], x)
        else:
            destroy = self.mode == "pipeload"
            x = self._run_pipeline(x, ledger, events, t0, destroy,
                                   apply_fn=prefill_apply)
        logits = self.fns["head"](self._resident["head"], x)
        nxt = jnp.argmax(logits, -1).astype(toks.dtype)[:, None]
        toks = jnp.concatenate([toks, nxt], axis=1)
        nxt.block_until_ready()
        prefill_s = time.perf_counter() - t0

        # ---- decode: one single-token pipeline round per remaining token
        def decode_apply(pos):
            def apply(k, w, h):
                h, caches[names[k]] = self._layer_decode(
                    k, w, h, caches[names[k]], pos)
                h.block_until_ready()
                return h
            return apply

        for step in range(1, new_tokens):
            pos = s0 + step - 1          # cache slot of the token we feed
            ensure_slots(pos + 1)        # paged: map the write page
            events.append((time.perf_counter() - t0, "token", str(step)))
            x = self.fns["embed"](self._resident["embed"], toks[:, -1:])
            if self.mode == "baseline":
                if self.expert is not None:
                    self.expert.begin_round()
                for k, name in enumerate(names):
                    x = decode_apply(pos)(k, self._baseline_weights[name], x)
            else:
                x = self._run_pipeline(x, ledger, events, t0,
                                       self.mode == "pipeload",
                                       apply_fn=decode_apply(pos))
            logits = self.fns["head"](self._resident["head"], x)
            nxt = jnp.argmax(logits, -1).astype(toks.dtype)[:, None]
            toks = jnp.concatenate([toks, nxt], axis=1)

        toks.block_until_ready()
        lat = time.perf_counter() - t0
        caches.clear()                    # free cache pages ...
        ledger.release(mapped["bytes"],   # ... and return them to the budget
                       owner="kv_pages")
        ledger.audit_check_drained("stream", "kv_pages")
        return toks, RunStats(self.mode, self.m, lat, ledger.peak, events,
                              loads=sum(1 for e in events
                                        if e[1] == "load_end"),
                              streamed_bytes=self._streamed(events),
                              new_tokens=new_tokens, prefill_s=prefill_s,
                              decode_s=lat - prefill_s,
                              cache_bytes=mapped["bytes"], kv_cache=True,
                              peak_breakdown=dict(ledger.peak_breakdown),
                              **self._expert_stats(snap),
                              **_fault_delta(fsnap))

    # ------------------------------------------------------------------
    def _draft_model(self, spec: SpecConfig) -> DraftModel:
        """One DraftModel per checkpoint dir, cached across runs (the
        benchmark calls run_generate repeatedly; re-reading the draft
        from disk each run would charge its load to the decode phase)."""
        cache = getattr(self, "_drafts", None)
        if cache is None:
            cache = self._drafts = {}
        key = str(spec.draft_dir)
        if key not in cache:
            cache[key] = DraftModel(spec.draft_dir, spec.draft_cfg)
        return cache[key]

    def _generate_spec(self, tokens, new_tokens: int, spec: SpecConfig
                       ) -> Tuple[jnp.ndarray, RunStats]:
        """Speculative draft/verify generation over the PAGED cache.

        Each round: the pinned draft proposes up to ``spec.depth``
        tokens (plain resident-model decodes, no weight stream), the
        target scores the whole window — last committed token + all
        proposals — in ONE stacked pipeline round
        (``layer_verify_paged``), and the longest agreeing prefix plus
        the target's own next pick commits.  Draft writes land on a
        copy-on-write BRANCH of the block table, so a rejected suffix
        rolls back by dropping page refcounts (O(pages), never a copy).
        Greedy outputs are token-identical to the non-speculative paths:
        every committed token is the argmax of target logits over an
        exactly-equal attention mask, regardless of what the draft
        proposed."""
        from repro.core.kv_pages import BlockTable, PagePool

        if not self.page_size:
            raise ValueError("speculative decoding needs the paged KV "
                             "cache: construct the engine with page_size")
        if "layer_verify_paged" not in self.fns:
            raise ValueError(
                "speculative decoding needs the stacked GQA verify path; "
                f"config {self.cfg.name} (attention={self.cfg.attention}, "
                f"sliding_window={self.cfg.sliding_window}) only supports "
                "the generic gather path")
        if new_tokens <= 0:
            return jnp.asarray(tokens), RunStats(self.mode, self.m, 0.0, 0,
                                                 [], kv_cache=True)
        toks_in = jnp.asarray(tokens)
        b, s0 = toks_in.shape
        if b != 1:
            raise ValueError("run_generate(speculative=...) is the "
                             "single-request path; use the scheduler's "
                             "spec_depth for batched serving")
        depth = max(1, int(spec.depth))
        w_max = depth + 1
        ps = self.page_size
        names = self.layer_names
        n = len(names)
        total = s0 + new_tokens
        nb = pages_for(total, ps)
        page_bytes = n * self.cfg.cache_bytes(1, ps)
        draft = self._draft_model(spec)
        draft_cache_bytes = draft.cache_bytes(1, total)
        extra = draft.total_bytes + draft_cache_bytes
        # feasibility at the WORST mapped-page count: the full-length
        # table plus one COW copy of the window's write page (branch
        # growth past the committed length is new pages the rollback
        # returns, but they are live during the verify round)
        cache_total = (nb + pages_for(w_max, ps) + 1) * page_bytes
        self._check_kv_budget(cache_total, extra_resident=extra)

        events: List[Tuple[float, str, str]] = []
        ledger = _Ledger(self.budget)
        fsnap = _fault_snap()
        t0 = time.perf_counter()
        self._ensure_aux(ledger, events, t0)
        draft.pin(ledger)
        events.append((time.perf_counter() - t0, "draft_pin",
                       str(draft.total_bytes)))
        ledger.acquire(draft_cache_bytes, owner="spec_headroom")

        toks: List[int] = [int(t) for t in np.asarray(toks_in).reshape(-1)]
        pool = PagePool(ps, page_bytes, ledger)
        pool_rows = nb + pages_for(w_max, ps) + 2
        table = BlockTable([pool.alloc() for _ in range(pages_for(s0, ps))])

        # ---- draft prefill (resident; overlaps nothing — it is cheap)
        _, dcaches = draft.prefill(toks_in, total)
        draft_pos = s0                   # draft-cache slots that match toks

        # ---- target prefill: pipelined cache capture, scattered into
        # the page pool (pad to the page boundary so rows split evenly)
        pad_len = pages_for(s0, ps) * ps
        caches: Dict[str, dict] = {}

        def prefill_apply(k, w, h):
            h, cache = self._layer_cache(k, w, h, pad_len)
            h.block_until_ready()
            caches[names[k]] = cache
            events.append((time.perf_counter() - t0, "cache_alloc",
                           names[k]))
            return h

        x = self.fns["embed"](self._resident["embed"], toks_in)
        if self.mode == "baseline":
            raise ValueError("speculative decoding needs a pipelined mode")
        x = self._run_pipeline(x, ledger, events, t0,
                               self.mode == "pipeload",
                               apply_fn=prefill_apply)
        logits = self.fns["head"](self._resident["head"], x)
        toks.append(int(jnp.argmax(logits, -1)[0]))
        generated = 1

        # physical pools: (pool_rows, ps, ...) per layer leaf, prefill
        # rows scattered into this request's own pages
        pids = jnp.asarray(table.pages, jnp.int32)
        pools: Dict[str, dict] = {}
        for name in names:
            pools[name] = jax.tree.map(
                lambda a: jnp.zeros((pool_rows, ps) + a.shape[2:],
                                    a.dtype).at[pids].set(
                    a[0].reshape((len(table.pages), ps) + a.shape[2:])),
                caches[name])
        caches.clear()
        prefill_s = time.perf_counter() - t0

        # ---- draft/verify rounds
        tr = _tele.get_tracer()
        spec_rounds = draft_tokens = accepted = 0
        while generated < new_tokens:
            k_prop = min(depth, new_tokens - generated - 1)
            # 1. draft proposes: catch up on committed tokens it has not
            # seen (<= 2 feeds after the first round), then chain k_prop
            # proposals off its own greedy picks
            logits_d = None
            props: List[int] = []
            with tr.span("draft_propose", depth=k_prop):
                for t in toks[draft_pos:]:
                    logits_d, dcaches = draft.decode(t, dcaches, draft_pos)
                    draft_pos += 1
                for j in range(k_prop):
                    nxt = int(jnp.argmax(logits_d, -1)[0])
                    props.append(nxt)
                    if j < k_prop - 1:
                        logits_d, dcaches = draft.decode(nxt, dcaches,
                                                         draft_pos)
                        draft_pos += 1
            # 2. branch the block table copy-on-write and map the verify
            # window's write range [pos0, pos0 + w_r)
            pos0 = len(toks) - 1         # slot of the last committed token
            w_r = k_prop + 1
            br = table.branch(pool)
            while len(br.pages) < pages_for(pos0 + w_r, ps):
                br.pages.append(pool.alloc())
            cow: List[Tuple[int, int]] = []
            for pidx in range(pos0 // ps,
                              pages_for(pos0 + w_r, ps)):
                swap = br.cow(pidx, pool)
                if swap is not None:
                    cow.append(swap)
            if cow:
                old = jnp.asarray([o for o, _ in cow], jnp.int32)
                new = jnp.asarray([nn for _, nn in cow], jnp.int32)
                pools = {name: jax.tree.map(
                    lambda a: a.at[new].set(a[old]), c)
                    for name, c in pools.items()}
            # 3. ONE stacked weight-stream round scores the window
            tab = np.zeros((1, nb), np.int32)
            tab[0, :len(br.pages)] = br.pages
            tab_j = jnp.asarray(tab)
            pos_j = jnp.asarray([pos0], jnp.int32)
            window = jnp.asarray([[toks[-1]] + props], jnp.int32)
            x = self.fns["embed"](self._resident["embed"], window)

            def verify_apply(k, w, h):
                h, pools[names[k]] = self.fns["layer_verify_paged"](
                    w, h, pools[names[k]], tab_j, pos_j)
                h.block_until_ready()
                return h

            events.append((time.perf_counter() - t0, "spec_round",
                           f"w={w_r}"))
            if tr.enabled:
                tr.instant("spec_verify", window=w_r)
            x = self._run_pipeline(x, ledger, events, t0,
                                   self.mode == "pipeload",
                                   apply_fn=verify_apply)
            logits = self.fns["head_all"](self._resident["head"], x)
            greedy = np.asarray(jnp.argmax(logits[0], -1))       # (w_r,)
            # 4. accept the longest agreeing prefix + the target's own
            # next pick (the "bonus" token — always correct: its context
            # is fully committed)
            a = 0
            while a < k_prop and props[a] == int(greedy[a]):
                a += 1
            old_len = len(toks)
            toks.extend(props[:a])
            toks.append(int(greedy[a]))
            generated += a + 1
            # 5. rollback: drop refcounts past the committed length —
            # rejected suffix pages unmap without copies — then commit
            # the branch as the new table
            br.rollback(pool, pages_for(pos0 + a + 1, ps))
            if tr.enabled:
                tr.instant("spec_rollback", accepted=a, proposed=k_prop)
            table.release_all(pool)
            table = br
            # draft-cache slots still agreeing with toks: everything it
            # had, minus proposals past the accepted prefix
            draft_pos = old_len + max(0, min(a, k_prop - 1))
            spec_rounds += 1
            draft_tokens += k_prop
            accepted += a

        out = jnp.asarray(np.asarray(toks)[None]).astype(toks_in.dtype)
        out.block_until_ready()
        lat = time.perf_counter() - t0
        table.release_all(pool)
        ledger.release(draft_cache_bytes, owner="spec_headroom")
        draft.unpin(ledger)
        ledger.audit_check_drained("stream", "kv_pages", "draft",
                                   "spec_headroom")
        return out, RunStats(self.mode, self.m, lat, ledger.peak, events,
                             loads=sum(1 for e in events
                                       if e[1] == "load_end"),
                             streamed_bytes=self._streamed(events),
                             new_tokens=new_tokens, prefill_s=prefill_s,
                             decode_s=lat - prefill_s,
                             cache_bytes=pool.mapped_peak_bytes,
                             kv_cache=True, spec_depth=depth,
                             spec_rounds=spec_rounds,
                             draft_tokens=draft_tokens,
                             accepted_tokens=accepted,
                             peak_breakdown=dict(ledger.peak_breakdown),
                             **_fault_delta(fsnap))

    # ------------------------------------------------------------------
    # Continuous-batching rounds (core/scheduler.py drives these)
    # ------------------------------------------------------------------
    def run_batch_round(self, ledger: _Ledger, events, t0, *,
                        decode_x=None, decode_caches: Optional[Dict] = None,
                        decode_pos=None, prefill_xs=(),
                        prefill_total: int = 0,
                        paged_pools: Optional[Dict] = None,
                        decode_tables=None,
                        chunk_x=None, chunk_tables=None, chunk_pos=None):
        """ONE pipeline round shared by every in-flight request.

        The §III machinery (loading agents, S_comp/S_dest/S_stop, in-order
        ledger grants) is untouched; only the Inference Agent's per-layer
        step changes: layer ``k`` streams through memory ONCE and is
        applied to

          * the stacked single-token states of all decoding requests
            (``decode_x`` (R, 1, D), per-layer caches with leading row
            dim R, RAGGED ``decode_pos`` (R,) — each request sits at its
            own cache slot), and
          * each joining request's cache-capturing prefill
            (``prefill_xs``: full-sequence states, caches padded to
            ``prefill_total`` slots),

        then destroyed.  This is the whole point of continuous batching:
        the dominant weight-stream cost is paid once per ROUND, not once
        per request.  The caller owns ``ledger``/``events``/``t0`` so
        accounting spans the serving session, not a single call.

        Paged serving (core/kv_pages.py) passes ``paged_pools`` — per
        layer, cache dicts with ``(P, page, ...)`` leaves — plus the
        stacked ``decode_tables`` (R, NB) block tables; the decode step
        then runs ``layer_decode_paged`` (Pallas block-table gather
        under ``attn_impl="pallas"``) and the pools are returned in the
        caches slot.  Prefill jobs are unchanged either way: the caller
        scatters their captured caches into pages at the boundary.

        Chunked prefill (the serving tier's long-prompt path) passes
        ``chunk_x`` (Bc, C, D) stacked C-token windows with their own
        ``chunk_tables`` (Bc, NB) / ``chunk_pos`` (Bc,): each streamed
        layer additionally applies ``layer_verify_paged`` to the chunk
        batch, writing the chunks' K/V straight into their requests'
        pages in-kernel — a long prompt joins decode rounds one chunk at
        a time instead of stalling them behind a monolithic prefill.

        Returns ``(decode_x', decode_caches', prefill_outs,
        prefill_caches, chunk_x')`` — the advanced decode states, per
        prefill job its final hidden states and captured per-layer
        caches, and the chunk batch's final hidden states (None when no
        chunks ran).
        """
        if self.mode == "baseline":
            raise ValueError(
                "run_batch_round needs a pipelined mode (pipeload / "
                "pipeswitch); baseline keeps the model resident and has "
                "no round to amortise")
        if paged_pools is not None and self.expert is not None:
            raise ValueError(
                "paged KV serving is not supported with expert-split "
                "MoE checkpoints yet; repartition whole-layer or drop "
                "page_size")
        names = self.layer_names
        prefill_caches: List[Dict[str, dict]] = [{} for _ in prefill_xs]

        if (decode_x is not None and decode_x.shape[1] > 1
                and paged_pools is None):
            raise ValueError(
                "stacked multi-token decode (speculative verify) needs "
                "paged pools; dense decode_caches take one token per "
                "round")
        if chunk_x is not None and paged_pools is None:
            raise ValueError(
                "chunked prefill needs paged pools (chunks write K/V "
                "through the block tables)")

        def apply_fn(k, w, state):
            dx, cx, pxs = state
            if dx is not None and paged_pools is not None:
                # W>1 stacked states = a speculative verify round: each
                # request's window [pos, pos+W) scores in one pass
                fn = (self.fns["layer_verify_paged"] if dx.shape[1] > 1
                      else self.fns["layer_decode_paged"])
                dx, paged_pools[names[k]] = self._dispatch(
                    k, fn, w, dx, paged_pools[names[k]], decode_tables,
                    decode_pos)
                self._sync(k, dx)
            elif dx is not None:
                dx, decode_caches[names[k]] = self._dispatch(
                    k, self._layer_decode, k, w, dx, decode_caches[names[k]],
                    decode_pos)
                self._sync(k, dx)
            if cx is not None:
                # chunk windows ride the same verify module at width C,
                # against their OWN tables/positions (disjoint writes:
                # chunk slots are prompt positions in the chunkers'
                # pages; any shared page gets bitwise-identical bytes)
                cx, paged_pools[names[k]] = self._dispatch(
                    k, self.fns["layer_verify_paged"], w, cx,
                    paged_pools[names[k]], chunk_tables, chunk_pos)
                self._sync(k, cx)
            nxt = []
            for i, px in enumerate(pxs):
                px, cache = self._dispatch(k, self._layer_cache, k, w, px,
                                           prefill_total)
                self._sync(k, px)
                prefill_caches[i][names[k]] = cache
                nxt.append(px)
            return dx, cx, nxt

        self._ensure_aux(ledger, events, t0)
        widest = [px.shape[0] * px.shape[1] for px in prefill_xs]
        if decode_x is not None:
            widest.append(decode_x.shape[0])
        if chunk_x is not None:
            widest.append(chunk_x.shape[0] * chunk_x.shape[1])
        self._bind_expert(ledger, events, t0,
                          round_tokens=max(widest, default=1))
        state = (decode_x, chunk_x, list(prefill_xs))
        dx, cx, pxs = self._run_pipeline(state, ledger, events, t0,
                                         destroy=self.mode == "pipeload",
                                         apply_fn=apply_fn)
        caches_out = paged_pools if paged_pools is not None else decode_caches
        return dx, caches_out, pxs, prefill_caches, cx

    def _kv_floor(self, cache_total: int, *,
                  expert_floor: Optional[int] = None,
                  extra_resident: int = 0) -> int:
        """Smallest budget that cannot deadlock a KV decode round holding
        ``cache_total`` bytes of cache pages: other layers + all pages +
        the pinned window + one streaming layer.  Non-destroying modes
        (baseline / pipeswitch) keep the WHOLE model resident for a round,
        so their floor is the full model + cache.  ``cache_total`` is the
        TOTAL reservation — for continuous batching, the sum over every
        in-flight request — which is what the scheduler's admission
        control feeds back in before granting a new request its pages.
        ``extra_resident`` adds run-scoped residents outside the four
        standard tiers — the speculative path's pinned draft model and
        its dense cache."""
        other = sum(s["bytes"] for s in self.shards.values()
                    if s["kind"] not in ("layer", "expert"))
        layer_sizes = [self.shards[nm]["bytes"] for nm in self.layer_names]
        if self.mode == "pipeload":
            pinned = sum(layer_sizes[:self.pin])
            streaming = max(layer_sizes[self.pin:], default=0)
        else:
            pinned, streaming = sum(layer_sizes), 0
        expert = 0
        if self.expert is not None:
            # ``expert_floor`` = the workload's shrinkable minimum (the
            # scheduler's feasibility checks pass it — admission can
            # evict the cache down to it); otherwise bound sessions hold
            # the live reservation and pre-run checks use the smallest
            # cache a single-token round can run with
            if expert_floor is not None:
                expert = expert_floor
            else:
                expert = (self.expert.reserved if self.expert.bound
                          else self.expert.min_ws)
        return (other + cache_total + pinned + streaming + expert
                + extra_resident)

    def _check_kv_budget(self, cache_total: int, *, inflight: int = 1,
                         expert_floor: Optional[int] = None,
                         extra_resident: int = 0):
        """Raise unless the budget clears the decode floor for the full
        multi-request reservation (``cache_total`` bytes across
        ``inflight`` concurrent requests); below it the pipeline deadlocks
        with every loader parked on S_stop.  ``expert_floor`` overrides
        the expert-cache term with the workload's shrinkable minimum
        (see ``_kv_floor``); ``extra_resident`` adds the speculative
        draft's pinned bytes."""
        if self.budget is None:
            return
        floor = self._kv_floor(cache_total, expert_floor=expert_floor,
                               extra_resident=extra_resident)
        if self.budget < floor:
            per_req = cache_total // max(inflight, 1)
            raise ValueError(
                f"budget {self.budget} below the KV decode floor {floor} "
                f"for {inflight} in-flight request(s) "
                f"(cache={cache_total} = {inflight} x {per_req} "
                f"cache-page bytes, plus other layers, the pinned window, "
                f"one streaming layer and — for expert-split MoE — the "
                f"expert cache); use the generation-aware "
                f"planner (Hermes.plan_generate) to pick a feasible "
                f"(num_agents, pin_window, max_inflight), or let the "
                f"scheduler queue the request until pages free up")
