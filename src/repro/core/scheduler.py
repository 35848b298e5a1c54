"""Continuous-batching scheduler: one weight stream serves every request.

PIPELOAD's dominant cost is streaming layer weights through the Loading
Agents, paid once per pipeline round — yet the single-request engine
spends each round on ONE sequence, so serving N users costs N full weight
streams per generated token.  The scheduler amortises the stream: each
round, layer ``k`` is loaded once, applied to the stacked single-token
hidden states of ALL in-flight requests (ragged positions — every request
sits at its own cache slot) and to the cache-capturing prefill of
requests admitted at this round boundary, then destroyed (``S_dest``).
Aggregate throughput scales with the in-flight count while the per-round
cost stays one weight stream.

Lifecycle (all transitions happen at round boundaries, except retirement
detection, which happens the instant a request's last token is sampled):

    submit() -> QUEUED -> [admission] -> PREFILLING -> DECODING -> DONE
                  ^                                       |
                  |            cache pages released       |
                  +------- (reusable at the SAME boundary)+

Memory protocol: every request's KV pages are charged to the engine's
``_Ledger`` — the same budget the streamed weights draw from.  Admission
is FIFO and blocks (requests wait in the queue) whenever the
post-admission decode floor

    other_bytes + pinned + all in-flight cache pages + one streaming layer

would exceed the budget, or the in-flight count would exceed
``max_inflight``.  Retirement is the cache analogue of ``S_dest``: the
round a request finishes, its pages are released immediately, so a queued
request can be admitted with the freed bytes at the very same boundary.

All caches are padded to ``max_total_len`` slots so stacked decode reuses
one jitted executable per batch size (padding past a request's position
is exactly masked out — softmax contributions are exact zeros — so
batched decoding is token-for-token identical to sequential runs).

**Paged mode** (``page_size`` set, core/kv_pages.py): instead of one
contiguous max-length reservation per request, the KV ledger bytes are
carved into fixed-size pages mapped through per-request block tables.

  * Admission charges only the request's PROMPT pages (pages a live
    sibling already mapped through the ``PrefixTree`` are refcount
    bumps — a fleet of requests behind one system prompt charges its
    prefix once), so the decode floor is pages-actually-mapped plus one
    page of headroom per in-flight request instead of
    ``inflight x max_total_len``.  A shared page holds K/V the
    sibling's prefill computed: bitwise what this request would have
    written when the prompts are the same LENGTH; a different length
    reuses values from a different prefill shape — equal up to float
    reassociation, so greedy can diverge at near-tie logits (the same
    caveat as preemption below).
  * Decode grows a request one page at a time as its position crosses a
    page boundary; writes into a shared page copy-on-write it first.
  * If growth cannot clear the floor, the YOUNGEST in-flight request is
    preempted — its pages are freed and it re-queues with its tokens so
    far (re-prefilled on re-admission); the oldest request always fits
    alone (submit() enforced it), so serving never deadlocks.  A
    re-prefill recomputes bit-identical K/V, but full-sequence prefill
    and incremental decode sum the softmax in different orders, so a
    preempted request's continuation can diverge from the sequential
    reference at float-tie tokens — preemption is a correctness-
    preserving overload valve, not part of the equivalence guarantee.
  * Retirement drops one reference per page: non-shared pages free (and
    re-enter the free list at the pool's high-water mark) the moment
    the request finishes; pages shared with a live sibling survive
    until the last sharer retires.

Physical page storage is one ``(rows, page_size, ...)`` array per layer
per cache leaf, sized once at construction (``max_inflight`` worst-case
tables + COW slack) so jitted decode shapes never change; the ledger
only ever charges MAPPED pages, and the decode attention gathers K/V
tiles through the block table (Pallas kernel under
``attn_impl="pallas"``, kernels/paged_decode.py).

**Serving tier** (multi-tenant SLO serving on top of the mechanisms
above):

  * *Priority classes with preemption.*  ``submit(..., priority=p)``
    orders the queue by ``(-priority, arrival_round, rid)`` and admission
    may BOUNCE an in-flight request back to the queue to make room for a
    strictly-higher-priority arrival — the victim is always the
    lowest-priority, youngest-admitted in-flight request, the same
    ordering growth-preemption uses, so the no-deadlock argument is
    unchanged: ``submit()`` proved every request fits alone, victims
    release their ledger bytes exactly (``release_all``), and a bounced
    request re-prefills from its tokens-so-far on re-admission.
    Preemption only ever flows downhill (never equal or higher
    priority), so a boundary's admission loop terminates and a
    bounded-priority trace cannot starve: high classes drain in finite
    rounds, then the bounced request is the queue head again.
  * *Chunked prefill* (``chunk_prefill=C``, paged mode, page-aligned:
    ``C`` rounds up to a page multiple).  A prompt longer than ``C``
    joins decode rounds as a sequence of C-token chunk jobs riding the
    stacked ``layer_verify_paged`` window (the speculative-verify
    module): each round streams the layers ONCE and applies them to the
    decode batch AND every in-flight chunk, writing the chunk's K/V
    straight into the request's pages in-kernel — so a long prompt costs
    ``ceil(L/C)`` decode-shaped rounds instead of stalling every
    in-flight decode behind one monolithic prefill round.  The final
    chunk is padded to width ``C`` by RE-feeding the preceding tokens at
    their own positions (bitwise-identical K/V rewrites — the draft
    catch-up trick), and its last column feeds the head for the first
    generated token.  The jnp verify path reuses the decode attention
    exactly, so chunked prefill is token-identical to unchunked serving
    up to the usual prefill-vs-decode float-reassociation caveat.
  * *Per-tenant prefix namespaces.*  ``submit(..., tenant=t)`` keys the
    radix prefix index by tenant (``PrefixNamespaces``): system prompts
    share pages WITHIN a tenant, never across — isolation is structural,
    and retirement in one tenant can never free another's pages.
  * *SLO accounting + shedding.*  ``slo=SLO(...)`` sets TTFT/TPOT
    targets in ROUNDS (the deterministic clock — a replayed trace meets
    or misses them identically on any machine); ``ServeStats`` reports
    p50/p99 TTFT and TPOT in rounds and seconds, preemption counts and
    goodput-under-SLO, and ``SLO.shed=True`` rejects queued requests
    whose TTFT target is already unattainable at admission time instead
    of burning rounds on doomed work.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import telemetry as _tele
from repro.core.engine import DraftModel, PipeloadEngine, _Ledger
from repro.core.kv_pages import (BlockTable, PagePool, PrefixNamespaces,
                                 pages_for)


@dataclasses.dataclass(frozen=True)
class SLO:
    """Service-level objectives in ROUNDS — the scheduler's deterministic
    clock, so a replayed trace attains or misses them identically on any
    machine (wall-clock percentiles are reported alongside, but policy
    decisions never read the wall clock).

    ``ttft_rounds``: a request attains its TTFT target when its first
    token lands within that many rounds of arrival (inclusive — 1 means
    "the arrival round itself").  ``tpot_rounds``: average rounds per
    subsequent token (1.0 = a token every round; only speculative
    serving goes below 1).  ``shed=True`` additionally REJECTS a queued
    request at admission time once its TTFT target is provably
    unattainable (queueing delay alone already exceeds it) — shedding
    doomed work is how goodput-under-SLO beats raw throughput under
    overload."""
    ttft_rounds: Optional[int] = None
    tpot_rounds: Optional[float] = None
    shed: bool = False


@dataclasses.dataclass
class Request:
    """One generation request; scheduler-owned fields below ``rid``."""
    rid: int
    prompt: np.ndarray            # (S,) int token ids
    max_new_tokens: int
    arrival_round: int = 0        # earliest boundary it may be admitted at
    priority: int = 0             # higher = admitted (and kept) first
    tenant: str = "default"       # prefix-namespace key
    # -- scheduler state ------------------------------------------------
    tokens: List[int] = dataclasses.field(default_factory=list)
    generated: int = 0
    admitted_round: int = -1
    finished_round: int = -1
    cache_bytes: int = 0          # ledger reservation while in flight
    table: Optional[BlockTable] = None   # paged mode: page ids + n_shared
    draft_pos: int = 0            # speculative: draft cache slots valid
    # -- chunked prefill ------------------------------------------------
    prefilling: bool = False      # True while chunks are still feeding
    prefill_pos: int = 0          # tokens whose K/V is already paged in
    # -- SLO accounting -------------------------------------------------
    born_round: int = 0           # original arrival (preemption re-queues
                                  # mutate arrival_round; TTFT uses this)
    first_token_round: int = -1
    rejected: bool = False        # shed by SLO admission control
    t_arrival: float = -1.0       # wall-clock marks (observability only)
    t_first: float = -1.0
    t_done: float = -1.0

    @property
    def done(self) -> bool:
        return self.generated >= self.max_new_tokens

    @property
    def pos(self) -> int:
        """Cache slot of the token about to be fed (current length - 1)."""
        return len(self.tokens) - 1


@dataclasses.dataclass
class ServeStats:
    rounds: int
    latency_s: float
    peak_bytes: int
    loads: int
    streamed_bytes: int
    new_tokens: int
    requests: int
    max_inflight_seen: int
    cache_bytes_peak: int
    events: List[Tuple[float, str, str]]
    # expert-streaming extras (0 for dense / whole-layer MoE serving)
    expert_hits: int = 0
    expert_misses: int = 0
    expert_evictions: int = 0
    expert_cache_bytes: int = 0
    unique_experts_per_round: float = 0.0
    # reproducibility: the RNG seed the serving trace was generated with
    # (None when the caller did not thread one)
    seed: Optional[int] = None
    # paged-KV extras (0 / dense defaults when page_size is unset)
    page_size: int = 0
    pages_allocated: int = 0       # pool allocs (fresh + free-list reuse)
    page_reuses: int = 0           # allocs served from the free list
    prefix_hit_pages: int = 0      # prompt pages shared via the PrefixTree
    cow_copies: int = 0            # copy-on-write page swaps
    preemptions: int = 0           # requests bounced back to the queue
    pool_pages_peak: int = 0       # high-water MAPPED page count
    # speculative-decoding extras (0 when spec_depth is unset)
    spec_depth: int = 0            # draft tokens proposed per round
    spec_rounds: int = 0           # verify rounds executed
    draft_tokens: int = 0          # proposals the draft emitted
    accepted_tokens: int = 0       # proposals the target committed
    # serving-tier extras (SLO / multi-tenant / chunked prefill)
    tenants: int = 0               # distinct tenant namespaces served
    chunk_size: int = 0            # chunked-prefill chunk tokens (0 = off)
    chunk_jobs: int = 0            # prefill chunks joined into rounds
    ttft_p50_rounds: float = 0.0   # rounds from arrival to first token
    ttft_p99_rounds: float = 0.0
    tpot_p50_rounds: float = 0.0   # rounds per subsequent token
    tpot_p99_rounds: float = 0.0
    ttft_p50_s: float = 0.0        # wall-clock mirrors (observability)
    ttft_p99_s: float = 0.0
    tpot_p50_s: float = 0.0
    tpot_p99_s: float = 0.0
    slo_attained: float = 1.0      # fraction of requests meeting the SLO
    goodput_tokens: int = 0        # tokens from requests meeting the SLO
    slo_rejections: int = 0        # requests shed at admission
    # policy trace for golden-file regression tests:
    # (kind, rid, round, t_wall) for every admit / preempt / retire /
    # reject decision, in order.  The first three fields are
    # deterministic under a fixed trace (no wall-clock terms — golden
    # tests pin only those); t_wall is the decision's wall-clock second
    # since the session's _t0, the same timeline as ``events`` and the
    # Request t_arrival/t_first/t_done marks, so policy decisions line
    # up with trace spans (observability only)
    policy: List[Tuple[str, int, int, float]] = dataclasses.field(
        default_factory=list)
    # prefetch fault-injection outcomes (REPRO_PREFETCH_FAULT_RATE),
    # wired from the telemetry metrics registry as per-session deltas
    retries: int = 0
    faults_absorbed: int = 0
    # per-owner byte shares at the session ledger's peak (sums exactly
    # to peak_bytes; additive — golden traces pin only `policy`)
    peak_breakdown: Dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def tokens_per_s(self) -> float:
        return self.new_tokens / self.latency_s if self.latency_s else 0.0

    @property
    def goodput_tokens_per_s(self) -> float:
        """Goodput-under-SLO: only tokens from requests that met every
        SLO target count (the serving-tier objective — raw tokens/s
        rewards work the user already gave up on)."""
        return (self.goodput_tokens / self.latency_s
                if self.latency_s else 0.0)

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

    def event_log(self, kinds=None):
        return [e for e in self.events if kinds is None or e[1] in kinds]


class BatchScheduler:
    """Round-boundary continuous batching over a ``PipeloadEngine``.

    ``max_total_len`` bounds every request's prompt + generation length;
    it fixes the padded cache shape so batched rounds compile once per
    batch size.  ``max_inflight`` caps concurrency; the budget caps it
    further through admission control (capacity-first: the planner's
    ``plan_generate(..., max_inflight=...)`` picks the triple).
    """

    def __init__(self, engine: PipeloadEngine, *, max_inflight: int = 4,
                 max_total_len: int = 128,
                 page_size: Optional[int] = None,
                 prefix_cache: bool = True,
                 seed: Optional[int] = None,
                 draft: Optional[DraftModel] = None,
                 spec_depth: int = 0,
                 chunk_prefill: int = 0,
                 slo: Optional[SLO] = None):
        if engine.mode == "baseline":
            raise ValueError("continuous batching needs a pipelined mode "
                             "(pipeload / pipeswitch)")
        self.engine = engine
        self.max_inflight = max(1, max_inflight)
        self.max_total_len = max_total_len
        # paged KV mode: explicit page_size wins, else inherit the
        # engine's (the planner threads its page-size pick through the
        # engine); 0/None = dense per-request reservations
        if page_size is None:
            page_size = engine.page_size
        self.page_size = page_size if page_size and page_size > 0 else None
        # speculative serving: a pinned draft proposes spec_depth tokens
        # per round per request and one stacked verify round scores them
        self.spec_depth = max(0, spec_depth) if draft is not None else 0
        self.draft = draft if self.spec_depth else None
        if self.spec_depth:
            if not self.page_size:
                raise ValueError(
                    "speculative serving needs paged KV (the verify "
                    "window rides the block tables); set page_size")
            if "layer_verify_paged" not in engine.fns:
                raise ValueError(
                    "engine's model fns lack layer_verify_paged "
                    "(speculative verify); architecture unsupported")
        # chunked prefill (serving tier): prompts longer than ``chunk``
        # tokens prefill C tokens per round through the stacked verify
        # window instead of one monolithic prefill round
        self.chunk = 0
        if chunk_prefill and chunk_prefill > 0:
            if not self.page_size:
                raise ValueError(
                    "chunked prefill needs paged KV (chunks write K/V "
                    "through the block tables); set page_size")
            if self.spec_depth:
                raise ValueError(
                    "chunked prefill and speculative serving are "
                    "mutually exclusive (both reshape the round); pick "
                    "one")
            if "layer_verify_paged" not in engine.fns:
                raise ValueError(
                    "engine's model fns lack layer_verify_paged (the "
                    "chunk window); architecture unsupported for "
                    "chunked prefill")
            # page-aligned chunks: non-final chunk boundaries land on
            # page boundaries, so a chunk never splits a page's writes
            # across rounds
            ps = self.page_size
            self.chunk = -(-int(chunk_prefill) // ps) * ps
        self.slo = slo
        self.slo_rejections = 0
        # (kind, rid, round, t_wall) policy decisions — the golden-trace
        # log (golden tests pin the first three, deterministic fields;
        # t_wall aligns each decision with the span-trace timeline)
        self.policy_log: List[Tuple[str, int, int, float]] = []
        self._chunk_jobs = 0
        # telemetry: registry counters cached once (reset() zeroes them
        # in place) + the session baseline for the fault-counter deltas
        m = _tele.metrics()
        self._m_admits = m.counter("sched.admits")
        self._m_preempts = m.counter("sched.preemptions")
        self._m_retires = m.counter("sched.retires")
        self._m_rejects = m.counter("sched.rejections")
        self._fault_base = _tele.counter_values("prefetch.retries",
                                                "prefetch.faults_absorbed")
        self.seed = seed
        self.queue: List[Request] = []   # by (-priority, arrival, rid)
        self.inflight: List[Request] = []
        self.done: Dict[int, Request] = {}
        self.round = 0
        self._next_rid = 0
        # per-request-row stacked state (rows parallel to self.inflight)
        self._caches: Optional[Dict[str, dict]] = None   # leaves (R, T, ...)
        # serving-session accounting: ONE ledger across all rounds, so
        # weights, caches and the pinned window share a single budget
        self.ledger = _Ledger(engine.budget)
        self.events: List[Tuple[float, str, str]] = []
        self._t0 = time.perf_counter()
        self._cache_resident = 0
        self._cache_peak = 0
        self._max_seen = 0
        self._per_req_cache = (len(engine.layer_names)
                               * engine.cfg.cache_bytes(1, max_total_len))
        # ---- paged-KV state (None/unused in dense mode) ----
        self.pool: Optional[PagePool] = None
        # per-tenant radix indexes over ONE shared pool: prefix pages
        # share within a tenant, never across (kv_pages.PrefixNamespaces)
        self.tree: Optional[PrefixNamespaces] = None
        self._pools: Optional[Dict[str, dict]] = None  # layer -> (P, ps, ..)
        self.preemptions = 0
        if self.page_size:
            if engine.expert is not None:
                raise ValueError(
                    "paged KV serving is not supported with expert-split "
                    "MoE checkpoints yet; repartition whole-layer or drop "
                    "page_size")
            ps = self.page_size
            # speculative verify writes K/V for the whole window
            # [pos, pos + depth]; the last round's window can run past
            # max_total_len, so tables carry the overhang slots (the
            # extra K/V is masked garbage, freed at retirement)
            self._nb = pages_for(max_total_len + self.spec_depth, ps)
            self._page_bytes = (len(engine.layer_names)
                                * engine.cfg.cache_bytes(1, ps))
            self.pool = PagePool(ps, self._page_bytes, self.ledger)
            self.tree = PrefixNamespaces(ps) if prefix_cache else None
            # fixed physical pool rows: worst-case tables + COW slack,
            # sized ONCE so jitted decode shapes never change (the
            # ledger charges only MAPPED pages; these rows are buffer)
            self._pool_rows = self.max_inflight * self._nb + 2
        # ---- speculative state (draft pinned for the whole session) ----
        self._draft_caches: Optional[Dict[str, dict]] = None  # (R, T, ...)
        self._spec_rounds = 0
        self._draft_tokens = 0
        self._accepted_tokens = 0
        if self.spec_depth:
            # per-request growth headroom: a verify round can map up to
            # a full window of fresh pages at once
            self._req_headroom = pages_for(self.spec_depth + 1,
                                           self.page_size)
            self._draft_total = max_total_len + self.spec_depth
            self._draft_cache_bytes = self.draft.cache_bytes(
                1, self._draft_total)
            self.draft.pin(self.ledger)   # resident for the session
        else:
            self._req_headroom = 1 if self.page_size else 0
        self._draft_pinned = self.spec_depth > 0
        self._expert_snap = (engine.expert.snapshot()
                             if engine.expert is not None else None)
        # the widest fetch this workload can lock (a max-length prompt's
        # prefill): admission may shrink the ExpertCache to this, never
        # below, and submit-time feasibility reasons from it
        self._expert_floor = (
            engine.expert.working_set_bytes(max_total_len)
            if engine.expert is not None else None)

    # ------------------------------------------------------------------
    def close(self):
        """End the serving session: unpin the draft's ledger bytes and
        tear down the engine's prefetch runtime (worker + drainer
        threads).  Idempotent."""
        if self.draft is not None and self._draft_pinned:
            self.draft.unpin(self.ledger)
            self._draft_pinned = False
        self.engine.close()

    def __enter__(self) -> "BatchScheduler":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int,
               arrival_round: int = 0, *, priority: int = 0,
               tenant: str = "default") -> int:
        """Queue a request; returns its id.

        ``priority`` orders admission (higher first; ties FIFO) and a
        strictly-higher-priority arrival may preempt the lowest-priority
        youngest in-flight request to get in.  ``tenant`` names the
        prefix namespace its prompt pages may share within.

        Raises if the request could NEVER be admitted — a prompt +
        generation length beyond ``max_total_len``, or a cache
        reservation that exceeds the budget floor even with zero other
        requests in flight (admission would otherwise deadlock the
        queue head forever)."""
        prompt = np.asarray(prompt).reshape(-1)
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if len(prompt) + max_new_tokens > self.max_total_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_total_len "
                f"{self.max_total_len}")
        if self.page_size:
            # worst case = every page of its final length, unshared,
            # PLUS the admission headroom (_fits_paged charges it per
            # in-flight request — without it a request whose total fits
            # the budget exactly would be accepted here yet never
            # admitted, spinning run() forever).  This is the guarantee
            # growth-with-preemption leans on: a request ALONE can
            # always map its next page (its whole verify window, in
            # speculative mode — where the draft and its cache row are
            # charged as extra residents too).
            worst = ((pages_for(len(prompt) + max_new_tokens
                                + self.spec_depth, self.page_size)
                      + self._req_headroom) * self._page_bytes)
            self.engine._check_kv_budget(
                worst, inflight=1, extra_resident=self._spec_resident(1))
            per_req = worst
        else:
            self.engine._check_kv_budget(self._per_req_cache, inflight=1,
                                         expert_floor=self._expert_floor)
            per_req = self._per_req_cache
        req = Request(self._next_rid, prompt, max_new_tokens,
                      arrival_round=max(arrival_round, 0),
                      priority=int(priority), tenant=str(tenant),
                      cache_bytes=per_req,
                      born_round=max(arrival_round, 0))
        self._next_rid += 1
        self.queue.append(req)
        self._sort_queue()
        return req.rid

    def _sort_queue(self) -> None:
        """Priority lattice: higher classes first, FIFO within a class
        (a preempted request re-enters with arrival_round = now, so it
        queues behind its class's newest arrivals — bounded classes
        cannot starve it)."""
        self.queue.sort(key=lambda r: (-r.priority, r.arrival_round,
                                       r.rid))

    def _tree(self, req: Request):
        """The request's tenant-namespace radix tree (None when prefix
        caching is off)."""
        return self.tree.tree(req.tenant) if self.tree is not None else None

    # ------------------------------------------------------------------
    def _fits(self, extra_cache: int) -> bool:
        """Would the decode floor still clear the budget after granting
        ``extra_cache`` more page bytes?  When the floor misses only
        because the ExpertCache holds the headroom, the cache shrinks
        first (LRU eviction releasing ledger bytes — the cache-side
        ``S_dest``), so a queued request's pages win over cold experts."""
        eng = self.engine
        if eng.budget is None:
            return True
        # until the expert engine is bound to THIS session's ledger the
        # live-reservation term does not exist (a binding left over from
        # an earlier run charged a dead ledger): reason from the
        # workload's expert floor so early admissions leave room for the
        # cache's minimum working set
        pre_bind = (eng.expert is not None
                    and not eng.expert.bound_to(self.ledger))
        kw = {"expert_floor": self._expert_floor} if pre_bind else {}
        floor = eng._kv_floor(self._cache_resident + extra_cache, **kw)
        if floor <= eng.budget:
            return True
        if eng.expert is not None and not pre_bind:
            if eng.expert.release_headroom(floor - eng.budget,
                                           floor=self._expert_floor):
                floor = eng._kv_floor(self._cache_resident + extra_cache)
                return floor <= eng.budget
        return False

    # ---- paged-mode admission / growth / preemption ------------------
    def _spec_resident(self, inflight: int) -> int:
        """Speculative mode's extra resident bytes: the pinned draft
        plus one dense draft-cache row per in-flight request."""
        if not self.spec_depth:
            return 0
        return (self.draft.total_bytes
                + inflight * self._draft_cache_bytes)

    def _fits_paged(self, extra_pages: int, inflight_after: int) -> bool:
        """Paged decode floor: pages actually mapped, plus the new pages,
        plus growth headroom per in-flight request (one page — a whole
        verify window of pages in speculative mode, where the pinned
        draft and its cache rows are charged as extra residents too)."""
        eng = self.engine
        if eng.budget is None:
            return True
        cache = ((self.pool.mapped_pages + extra_pages
                  + inflight_after * self._req_headroom)
                 * self._page_bytes)
        return (eng._kv_floor(
            cache, extra_resident=self._spec_resident(inflight_after))
            <= eng.budget)

    def _admit_one_paged(self, req: Request, inflight_after: int) -> bool:
        """Map the request's prompt pages (prefix-tree hits are refcount
        bumps, charged once across the fleet); False = does not fit at
        this boundary."""
        toks = req.tokens or [int(t) for t in req.prompt]
        n_pages = pages_for(len(toks), self.page_size)
        tree = self._tree(req)      # tenant namespace: within-tenant hits
        walk = tree.walk(toks) if tree is not None else None
        shared = len(walk[0]) if walk is not None else 0
        if not self._fits_paged(n_pages - shared, inflight_after):
            return False
        self.pool.detail = f"req{req.rid}"
        if tree is not None:
            pids, n_shared = tree.insert(toks, self.pool, walk=walk)
        else:
            pids, n_shared = [self.pool.alloc()
                              for _ in range(n_pages)], 0
        self.pool.detail = None
        req.table = BlockTable(pids, n_shared)
        req.tokens = toks
        if self.chunk and len(toks) > self.chunk:
            # long prompt: feed it C tokens per round as chunk jobs
            # (pages are all mapped already; chunking spreads the
            # COMPUTE, not the reservation)
            req.prefilling = True
            req.prefill_pos = 0
        if self.spec_depth:
            # the request's dense draft-cache row lives as long as the
            # request is in flight (never blocks: _fits_paged charged it
            # via _spec_resident, and at a boundary nothing streams)
            self.ledger.acquire(self._draft_cache_bytes,
                                owner="spec_headroom",
                                detail=f"req{req.rid}")
        return True

    def _preempt(self, victim: Request) -> None:
        """Bounce ``victim`` back to the queue, releasing its ledger
        bytes exactly (non-shared pages in paged mode, the whole dense
        reservation otherwise); it re-prefills from its tokens so far on
        re-admission."""
        idx = self.inflight.index(victim)
        if self.page_size:
            self.pool.detail = f"req{victim.rid}"
            victim.table.release_all(self.pool, self._tree(victim))
            self.pool.detail = None
            if self.spec_depth:
                self._draft_caches = self._rows_keep(
                    self._draft_caches,
                    [i for i in range(len(self.inflight)) if i != idx])
                self.ledger.release(self._draft_cache_bytes,
                                    owner="spec_headroom",
                                    detail=f"req{victim.rid}")
        else:
            self.ledger.release(victim.cache_bytes, owner="kv_pages",
                                detail=f"req{victim.rid}")
            self._cache_resident -= victim.cache_bytes
            self._drop_rows([i for i in range(len(self.inflight))
                             if i != idx])
        self.inflight.pop(idx)
        victim.admitted_round = -1
        victim.arrival_round = self.round
        victim.prefilling = False
        victim.prefill_pos = 0
        self.queue.append(victim)
        self._sort_queue()
        self.preemptions += 1
        now = time.perf_counter() - self._t0
        self.events.append((now, "preempt", f"req{victim.rid}"))
        self.policy_log.append(("preempt", victim.rid, self.round, now))
        self._m_preempts.inc()
        tr = _tele.get_tracer()
        if tr.enabled:
            tr.instant("preempt", rid=victim.rid, round=self.round)

    def _victim(self, below: Optional[int] = None) -> Optional[Request]:
        """The preemption victim: lowest priority first, youngest
        admission within a class — the generalisation of the original
        youngest-first order (all priorities equal reduces to it).
        ``below`` restricts to strictly-lower-priority victims (admission
        preemption only flows downhill; growth passes None and may pick
        any request, including the grower itself)."""
        cands = [r for r in self.inflight
                 if below is None or r.priority < below]
        if not cands:
            return None
        order = {id(r): i for i, r in enumerate(self.inflight)}
        return min(cands, key=lambda r: (r.priority, -order[id(r)]))

    def _alloc_with_preemption(self, req: Request) -> Optional[int]:
        """Map one more page for ``req``, preempting the lowest-priority
        YOUNGEST in-flight request — possibly ``req`` itself — while the
        floor would not clear (within a priority class this is the
        original strict age order: an older request's progress is never
        sacrificed for a younger grower).  Returns None when ``req`` was
        the victim; otherwise always succeeds — once ``req`` is alone,
        submit() guaranteed its worst case fits."""
        while not self._fits_paged(1, 0) and len(self.inflight) > 1:
            victim = self._victim()
            self._preempt(victim)
            if victim is req:
                return None
        self.pool.detail = f"req{req.rid}"   # _preempt cleared it
        pid = self.pool.alloc()
        self.pool.detail = None
        if pid >= self._pool_rows:
            raise RuntimeError(
                f"page pool overflow: page {pid} >= {self._pool_rows} "
                f"physical rows (max_inflight x table width + COW slack)"
            )   # unreachable: admission + growth bound live pages
        return pid

    def _grow_pages(self):
        """Round boundary, before admission: map each in-flight
        request's WRITE RANGE — the one page its next token lands in,
        or, in speculative mode, every page the verify window
        [pos, pos + depth] touches — growing across page boundaries and
        copy-on-writing shared pages before their first divergent
        write."""
        if not self.inflight:
            return
        cow: List[Tuple[Request, int, int]] = []
        for req in list(self.inflight):
            if req not in self.inflight:    # preempted by an earlier grower
                continue
            if req.prefilling:
                # chunked prefill: every page was mapped at admission and
                # chunk writes land in the request's own prompt pages (or
                # rewrite shared pages with bitwise-identical K/V), so a
                # prefilling request neither grows nor copy-on-writes
                continue
            t = req.table
            lo = req.pos // self.page_size
            hi = (req.pos + self.spec_depth) // self.page_size
            while len(t.pages) <= hi:
                pid = self._alloc_with_preemption(req)
                if pid is None:             # req itself was the victim
                    break
                t.pages.append(pid)
            for pidx in range(lo, hi + 1):
                if req not in self.inflight:
                    break
                pid = t.pages[pidx]
                if not self.pool.is_shared(pid):
                    continue
                new = self._alloc_with_preemption(req)
                if new is None:             # req preempted: refs already
                    break                   # dropped by release_all
                cow.append((req, pid, new))
                # usually the sibling keeps the old page — but if the
                # COW alloc preempted that sibling, this drop is the
                # LAST reference and the tree node must go with it
                # (prefix pages only ever index the OWNER's tenant tree)
                tree = self._tree(req)
                if self.pool.release(pid) and tree is not None:
                    tree.forget(pid)
                t.pages[pidx] = new
        # drop copies whose OWNER was preempted after queuing them (its
        # freed target id may already be re-mapped by a later grower —
        # a stale entry would make the batched scatter write the same
        # destination twice), then copy page contents old -> new in one
        # batched update per leaf
        cow = [(o, n) for r, o, n in cow if r in self.inflight]
        self.pool.stats.cow_copies += len(cow)   # copies actually made
        self.pool._m_cow.inc(len(cow))
        tr = _tele.get_tracer()
        if tr.enabled and cow:
            tr.instant("page_cow", copies=len(cow), round=self.round)
        if cow:
            old = jnp.asarray([o for o, _ in cow], jnp.int32)
            new = jnp.asarray([n for _, n in cow], jnp.int32)
            self._pools = {
                name: jax.tree.map(lambda a: a.at[new].set(a[old]), c)
                for name, c in self._pools.items()}

    def _pool_like(self, cache):
        """Zeroed physical page array(s) shaped for ``cache`` leaves:
        (B, T, ...) -> (pool_rows, page_size, ...).  The ONE place the
        pool layout is defined — warmup compiles against arrays built
        here, so serving shapes always match the warmed executables."""
        return jax.tree.map(
            lambda a: jnp.zeros(
                (self._pool_rows, self.page_size) + a.shape[2:], a.dtype),
            cache)

    def _ensure_pool_arrays(self, template: Dict[str, dict]):
        """Create the physical page arrays from the first prefill's
        cache shapes: one (rows, page_size, ...) array per layer per
        cache leaf, sized once (see class docstring)."""
        if self._pools is not None:
            return
        self._pools = {name: self._pool_like(c)
                       for name, c in template.items()}

    def _scatter_prefills(self, reqs: List[Request],
                          caches: List[Dict[str, dict]]):
        """Write the boundary's captured prefill caches into each
        request's OWNED pages — ONE batched scatter per layer per cache
        leaf (a per-request loop would copy the whole physical pool
        once per update).  Shared prefix pages are skipped: a sibling
        already wrote identical K/V, and a shared partial page may hold
        the sibling's generated tokens past this prompt (masked by the
        valid-length mask, clobbered by nothing)."""
        ps = self.page_size
        owned = [(r, c) for r, c in zip(reqs, caches)
                 if len(r.table.pages) > r.table.n_shared]
        if not owned:
            return
        self._ensure_pool_arrays(owned[0][1])
        pids = jnp.asarray([pid for r, _ in owned
                            for pid in r.table.pages[r.table.n_shared:]],
                           jnp.int32)

        def rows(a, t):
            lo, hi = t.n_shared * ps, len(t.pages) * ps
            return a[0, lo:hi].reshape((len(t.pages) - t.n_shared, ps)
                                       + a.shape[2:])

        for name in self._pools:
            blocks = [jax.tree.map(lambda a, t=r.table: rows(a, t), c[name])
                      for r, c in owned]
            stacked = (blocks[0] if len(blocks) == 1 else jax.tree.map(
                lambda *xs: jnp.concatenate(xs), *blocks))
            self._pools[name] = jax.tree.map(
                lambda leaf, rr: leaf.at[pids].set(rr.astype(leaf.dtype)),
                self._pools[name], stacked)

    def _chunk_rounds(self, req: Request) -> int:
        """Rounds this request's prefill will take once admitted (1 for
        the monolithic path; ``ceil(L / C)`` chunk rounds otherwise)."""
        n = len(req.tokens) or len(req.prompt)
        if not (self.chunk and self.page_size and n > self.chunk):
            return 1
        return -(-n // self.chunk)

    def _shed(self, req: Request) -> bool:
        """SLO admission control: reject a request whose TTFT target is
        already unattainable — even admitted THIS boundary, its first
        token cannot land inside the target (queueing delay + its own
        prefill rounds already exceed it).  Burning rounds on it would
        only push other requests past their targets too."""
        if (self.slo is None or not self.slo.shed
                or self.slo.ttft_rounds is None):
            return False
        if req.first_token_round >= 0:
            # a preempted request already delivered its first token; its
            # TTFT is decided — bouncing it again cannot be shed
            return False
        best_ttft = (self.round - req.born_round) + self._chunk_rounds(req)
        if best_ttft <= self.slo.ttft_rounds:
            return False
        self.queue.remove(req)
        req.rejected = True
        req.finished_round = self.round
        self.done[req.rid] = req
        self.slo_rejections += 1
        now = time.perf_counter() - self._t0
        self.events.append((now, "reject", f"req{req.rid}"))
        self.policy_log.append(("reject", req.rid, self.round, now))
        self._m_rejects.inc()
        tr = _tele.get_tracer()
        if tr.enabled:
            tr.instant("reject", rid=req.rid, round=self.round)
        return True

    def _reserve(self, req: Request, inflight_after: int) -> bool:
        """Try to reserve the request's cache at this boundary (maps
        pages / acquires the dense reservation on success)."""
        if self.page_size:
            return self._admit_one_paged(req, inflight_after)
        if not self._fits(req.cache_bytes):
            return False
        # reserve the request's pages for its whole lifetime (never
        # blocks: _fits checked the floor, and at a boundary nothing is
        # streaming)
        self.ledger.acquire(req.cache_bytes, owner="kv_pages",
                            detail=f"req{req.rid}")
        self._cache_resident += req.cache_bytes
        self._cache_peak = max(self._cache_peak, self._cache_resident)
        # a preempted request resumes from its tokens so far (re-prefill),
        # a fresh one starts from its prompt
        req.tokens = req.tokens or list(map(int, req.prompt))
        return True

    def _admit(self) -> List[Request]:
        """Priority-ordered admission at the current boundary.  Strict
        head-of-line WITHIN the eligible queue (sorted by priority class,
        FIFO inside a class): skipping the head could never help (dense
        mode reserves one padded size for everyone; paged mode's head is
        also the next to shrink via sharing); blocking keeps the order
        fair and is deadlock-free (submit() rejected anything that can't
        fit alone, and in-flight requests always retire in finite
        rounds).  A head that does not fit may PREEMPT strictly-lower-
        priority in-flight requests (lowest class, youngest first) for
        both a concurrency slot and cache room — preemption only flows
        downhill, so a boundary's loop terminates: each bounced request
        re-queues behind its own class and can only displace still-lower
        ones."""
        admitted: List[Request] = []
        while self.queue:
            eligible = [r for r in self.queue
                        if r.arrival_round <= self.round]
            if not eligible:
                break
            req = eligible[0]           # queue order: priority, then FIFO
            if self._shed(req):
                continue
            # concurrency slot: bounce a strictly-lower-priority victim
            if len(self.inflight) + len(admitted) >= self.max_inflight:
                victim = self._victim(below=req.priority)
                if victim is None:
                    break
                self._preempt(victim)
                continue                # victim re-queued; re-evaluate
            ok = self._reserve(req, len(self.inflight) + len(admitted) + 1)
            while not ok:
                victim = self._victim(below=req.priority)
                if victim is None:
                    break
                self._preempt(victim)
                ok = self._reserve(req,
                                   len(self.inflight) + len(admitted) + 1)
            if not ok:
                break
            self.queue.remove(req)
            req.admitted_round = self.round
            now = time.perf_counter() - self._t0
            self.events.append((now, "admit", f"req{req.rid}"))
            self.policy_log.append(("admit", req.rid, self.round, now))
            self._m_admits.inc()
            tr = _tele.get_tracer()
            if tr.enabled:
                tr.instant("admit", rid=req.rid, round=self.round)
            admitted.append(req)
        return admitted

    def _retire(self, finished: List[Request]):
        """S_dest for cache pages: release the ledger bytes the moment a
        request completes so the next boundary can re-grant them.  Paged
        mode drops one reference per page — pages shared with a live
        sibling survive until the LAST sharer retires (exact-drain at
        page granularity)."""
        for req in finished:
            if self.page_size:
                self.pool.detail = f"req{req.rid}"
                req.table.release_all(self.pool, self._tree(req))
                self.pool.detail = None
                if self.spec_depth:
                    self.ledger.release(self._draft_cache_bytes,
                                        owner="spec_headroom",
                                        detail=f"req{req.rid}")
            else:
                self.ledger.release(req.cache_bytes, owner="kv_pages",
                                    detail=f"req{req.rid}")
                self._cache_resident -= req.cache_bytes
            req.finished_round = self.round
            req.t_done = time.perf_counter() - self._t0
            self.done[req.rid] = req
            self.events.append((req.t_done, "retire", f"req{req.rid}"))
            # t_wall reuses the retirement mark already stamped on the
            # Request, so the policy trace and t_done agree exactly
            self.policy_log.append(("retire", req.rid, self.round,
                                    req.t_done))
            self._m_retires.inc()
            tr = _tele.get_tracer()
            if tr.enabled:
                tr.instant("retire", rid=req.rid, round=self.round)

    def _drop_rows(self, keep: List[int]):
        if self._caches is None:
            return
        if not keep:
            self._caches = None
            return
        idx = np.asarray(keep)
        self._caches = {name: jax.tree.map(lambda a: a[idx], c)
                        for name, c in self._caches.items()}

    def _append_rows(self, new_caches: List[Dict[str, dict]]):
        stacks = ([self._caches] if self._caches is not None else []) \
            + new_caches
        if not stacks:
            return
        if len(stacks) == 1:
            self._caches = stacks[0]
            return
        self._caches = {
            name: jax.tree.map(lambda *xs: jnp.concatenate(xs),
                               *(s[name] for s in stacks))
            for name in stacks[0]}

    # ---- speculative drafting (rows parallel to self.inflight) -------
    @staticmethod
    def _rows_keep(stack, keep: List[int]):
        """Row-filter a stacked cache dict (leaves (R, T, ...))."""
        if stack is None or not keep:
            return None
        idx = np.asarray(keep)
        return {name: jax.tree.map(lambda a: a[idx], c)
                for name, c in stack.items()}

    @staticmethod
    def _rows_concat(stacks):
        """Concatenate stacked cache dicts along the row dim."""
        stacks = [s for s in stacks if s is not None]
        if not stacks:
            return None
        if len(stacks) == 1:
            return stacks[0]
        return {name: jax.tree.map(lambda *xs: jnp.concatenate(xs),
                                   *(s[name] for s in stacks))
                for name in stacks[0]}

    def _draft_propose(self) -> List[List[int]]:
        tr = _tele.get_tracer()
        if not tr.enabled:
            return self._draft_propose_inner()
        with tr.span("draft_propose", rows=len(self.inflight),
                     depth=self.spec_depth):
            return self._draft_propose_inner()

    def _draft_propose_inner(self) -> List[List[int]]:
        """One stacked draft pass over every in-flight request: catch the
        draft cache up to the committed tokens, then chain ``spec_depth``
        greedy proposals per row.

        Rows may need different catch-up counts (1 after a partial
        accept, 2 after a full accept — the bonus token was never drafted).
        The batch feeds every row its last ``C = max(gap)`` committed
        tokens at their own slots: rows with a smaller gap RE-feed tokens
        already in their draft cache, overwriting those slots with
        bitwise-identical K/V (K/V depend only on token and position), so
        one jitted executable serves the ragged batch."""
        reqs = self.inflight
        c = max(len(r.tokens) - r.draft_pos for r in reqs)
        logits = None
        for i in range(c):
            toks = np.asarray([[r.tokens[len(r.tokens) - c + i]]
                               for r in reqs], np.int32)
            pos = np.asarray([len(r.tokens) - c + i for r in reqs],
                             np.int32)
            logits, self._draft_caches = self.draft.decode_batch(
                toks, self._draft_caches, pos)
        for r in reqs:
            r.draft_pos = len(r.tokens)
        props: List[List[int]] = [[] for _ in reqs]
        cur = np.asarray(jnp.argmax(logits, -1), np.int32)      # (R,)
        for j in range(self.spec_depth):
            for i in range(len(reqs)):
                props[i].append(int(cur[i]))
            if j < self.spec_depth - 1:
                pos = np.asarray([len(r.tokens) + j for r in reqs],
                                 np.int32)
                logits, self._draft_caches = self.draft.decode_batch(
                    cur[:, None], self._draft_caches, pos)
                cur = np.asarray(jnp.argmax(logits, -1), np.int32)
        return props

    # ------------------------------------------------------------------
    def _first_token(self, req: Request) -> None:
        """TTFT bookkeeping: called right before a request's FIRST
        generated token is appended (re-admitted preempted requests have
        generated > 0 and keep their original mark)."""
        if req.generated == 0 and req.first_token_round < 0:
            req.first_token_round = self.round
            req.t_first = time.perf_counter() - self._t0

    def _ensure_chunk_pools(self) -> None:
        """Chunk jobs write K/V straight into the physical pools, so the
        pool arrays must exist before the first chunk round — even when
        no monolithic prefill ever captured a cache template.  Builds the
        template from one transient layer load (warmup does this ahead
        of time; this is the cold-start fallback)."""
        if self._pools is not None:
            return
        eng = self.engine
        eng._ensure_aux(self.ledger, self.events, self._t0)
        emb = eng._resident["embed"]
        w0 = eng._load(eng.layer_names[0])
        x1 = eng.fns["embed"](emb, jnp.zeros((1, 1), jnp.int32))
        _, c1 = eng._layer_cache(0, w0, x1, self._nb * self.page_size)
        del w0
        self._ensure_pool_arrays({name: c1 for name in eng.layer_names})

    def step(self) -> bool:
        """One round boundary + (if there is work) one pipeline round.
        Returns False once every submitted request has retired.  Traced
        as ``serve_round``: its time outside the round's ``stream_round``
        is the scheduler's own host work."""
        tr = _tele.get_tracer()
        if not tr.enabled:
            return self._step(None)
        with tr.span("serve_round", round=self.round) as span:
            return self._step(span)

    def _step(self, span) -> bool:
        eng = self.engine
        now = time.perf_counter() - self._t0
        for r in self.queue:
            # wall-clock arrival mark: the first boundary at/after the
            # request's arrival round (rounds are the policy clock; the
            # wall marks only feed observability percentiles)
            if r.arrival_round <= self.round and r.t_arrival < 0:
                r.t_arrival = now
        if self.page_size:
            # map every decoder's write page first (may preempt), THEN
            # admit into whatever room is left
            self._grow_pages()
        admitted = self._admit()
        if not self.inflight and not admitted:
            if not self.queue:
                return False
            # idle gap: fast-forward to the next arrival (no weight stream)
            self.round = max(self.round + 1,
                             min(r.arrival_round for r in self.queue))
            return True

        fns, t0 = eng.fns, self._t0
        self.events.append((time.perf_counter() - t0, "round",
                            str(self.round)))
        tr = _tele.get_tracer()
        if span is not None:
            span.set(rows=len(self.inflight) + len(admitted),
                     stream=eng.runtime.streams)
        # serving-tier round shape: DECODERS advance one token through
        # the stacked decode batch; CHUNKERS (mid-chunked-prefill, plus
        # this boundary's long-prompt admissions) feed one C-token chunk
        # each through the stacked verify window; unchunked admissions
        # run the monolithic cache-capturing prefill
        decoders = [r for r in self.inflight if not r.prefilling]
        chunkers = ([r for r in self.inflight if r.prefilling]
                    + [r for r in admitted if r.prefilling])
        pre_admits = [r for r in admitted if not r.prefilling]
        # ---- build the decode batch (stacked last tokens, ragged pos;
        # speculative mode widens each row to its verify window
        # [last committed token, draft proposals...])
        dec_x = dec_pos = props = None
        if decoders:
            emb = eng._resident.get("embed")
            if emb is None:
                eng._ensure_aux(self.ledger, self.events, t0)
                emb = eng._resident["embed"]
            if self.spec_depth:
                # spec mode never chunks (ctor enforces it), so the
                # decode rows stay parallel to self.inflight
                props = self._draft_propose()
                last = np.asarray(
                    [[r.tokens[-1]] + props[i]
                     for i, r in enumerate(decoders)], np.int32)
            else:
                last = np.asarray([[r.tokens[-1]] for r in decoders],
                                  np.int32)
            dec_x = fns["embed"](emb, jnp.asarray(last))
            dec_pos = jnp.asarray([r.pos for r in decoders], jnp.int32)
        # ---- build the stacked chunk batch: row i feeds C tokens at
        # positions [start, start + C); the FINAL chunk slides its
        # window back to [L - C, L) — the overlap RE-feeds tokens whose
        # K/V is already paged in, rewriting identical bytes (K/V depend
        # only on token and position), so one jitted (Bc, C) executable
        # serves every chunk round
        chunk_x = chunk_tables = chunk_pos = None
        chunk_meta: List[Tuple[Request, int]] = []   # (req, window_end)
        if chunkers:
            eng._ensure_aux(self.ledger, self.events, t0)
            emb = eng._resident["embed"]
            self._ensure_chunk_pools()
            c = self.chunk
            rows, starts = [], []
            for r in chunkers:
                n = len(r.tokens)
                w0 = min(r.prefill_pos, n - c)
                rows.append(r.tokens[w0:w0 + c])
                starts.append(w0)
                chunk_meta.append((r, w0 + c))
            chunk_x = fns["embed"](
                emb, jnp.asarray(np.asarray(rows, np.int32)))
            chunk_pos = jnp.asarray(starts, jnp.int32)
            tb = np.zeros((len(chunkers), self._nb), np.int32)
            for i, r in enumerate(chunkers):
                tb[i, :len(r.table.pages)] = r.table.pages
            chunk_tables = jnp.asarray(tb)
            self._chunk_jobs += len(chunkers)
            if tr.enabled:
                for (r, end) in chunk_meta:
                    tr.instant("chunk_job", rid=r.rid, round=self.round,
                               end=end)
        # ---- build prefill jobs for this boundary's admissions
        pre_xs = []
        if pre_admits:
            eng._ensure_aux(self.ledger, self.events, t0)
            emb = eng._resident["embed"]
            for req in pre_admits:
                toks = jnp.asarray(np.asarray(req.tokens, np.int32)[None])
                pre_xs.append(fns["embed"](emb, toks))

        chunk_out = None
        if self.page_size:
            # stacked block tables, padded with page 0 (masked slots)
            dec_tables = None
            if dec_x is not None:
                tb = np.zeros((len(decoders), self._nb), np.int32)
                for i, r in enumerate(decoders):
                    tb[i, :len(r.table.pages)] = r.table.pages
                dec_tables = jnp.asarray(tb)
            paged_work = dec_x is not None or chunk_x is not None
            dec_x, pools, pre_outs, pre_caches, chunk_out = \
                eng.run_batch_round(
                    self.ledger, self.events, t0,
                    decode_x=dec_x,
                    decode_pos=dec_pos,
                    prefill_xs=pre_xs,
                    prefill_total=self._nb * self.page_size,
                    paged_pools=(self._pools if paged_work else None),
                    decode_tables=dec_tables,
                    chunk_x=chunk_x,
                    chunk_tables=chunk_tables,
                    chunk_pos=chunk_pos)
            if paged_work:
                self._pools = pools
            self._scatter_prefills(pre_admits, pre_caches)
        else:
            dec_x, caches, pre_outs, pre_caches, _ = eng.run_batch_round(
                self.ledger, self.events, t0,
                decode_x=dec_x,
                decode_caches=self._caches,
                decode_pos=dec_pos,
                prefill_xs=pre_xs,
                prefill_total=self.max_total_len)
            self._caches = caches

        # ---- heads: one greedy token per request this round — or, in
        # speculative mode, the accepted proposal prefix plus the
        # target's bonus token
        head = eng._resident["head"]
        if dec_x is not None and self.spec_depth:
            logits = fns["head_all"](head, dec_x)              # (R, W, V)
            greedy = np.asarray(jnp.argmax(logits, -1))        # (R, W)
            self._spec_rounds += 1
            for row, req in enumerate(decoders):
                prop = props[row]
                a = 0
                while a < len(prop) and prop[a] == int(greedy[row, a]):
                    a += 1
                # accepted prefix + the target's token after it, clamped
                # to the request's remaining token allowance (any prefix
                # of the commit list is the exact greedy continuation)
                remaining = req.max_new_tokens - req.generated
                commit = (prop[:a] + [int(greedy[row, a])])[:remaining]
                old_len = len(req.tokens)
                req.tokens.extend(commit)
                req.generated += len(commit)
                # draft slots old_len..old_len+depth-2 hold the proposal
                # K/V; they stay valid while the proposal matched the
                # committed token
                req.draft_pos = old_len + max(
                    0, min(a, self.spec_depth - 1, len(commit)))
                # count only proposals that could possibly commit — the
                # window always spans the full depth (uniform jitted
                # shapes), but near max_new_tokens the tail is clamped
                # away and should not read as rejections
                self._draft_tokens += min(len(prop), remaining)
                self._accepted_tokens += min(a, remaining)
        elif dec_x is not None:
            logits = fns["head"](head, dec_x)                  # (R, V)
            nxt = np.asarray(jnp.argmax(logits, -1))
            for row, req in enumerate(decoders):
                req.tokens.append(int(nxt[row]))
                req.generated += 1
        if chunk_out is not None:
            # head reads the window's LAST column — only meaningful for
            # a FINAL chunk, whose last column sits at the prompt's last
            # token; non-final rows just advance their chunk cursor
            logits = fns["head"](head, chunk_out)              # (Bc, V)
            nxt = np.asarray(jnp.argmax(logits, -1))
            for i, (req, end) in enumerate(chunk_meta):
                if end >= len(req.tokens):       # final chunk: sample
                    req.prefilling = False
                    req.prefill_pos = len(req.tokens)
                    self._first_token(req)
                    req.tokens.append(int(nxt[i]))
                    req.generated += 1
                else:
                    req.prefill_pos = end
        for i, req in enumerate(pre_admits):
            logits = fns["head"](head, pre_outs[i])            # (1, V)
            self._first_token(req)
            req.tokens.append(int(jnp.argmax(logits, -1)[0]))
            req.generated += 1           # re-prefills resume, not reset
        if self.spec_depth and admitted:
            # seed each admission's draft-cache row from its own prompt
            # prefill (the generated first token is caught up next round)
            rows = []
            for req in admitted:
                toks = jnp.asarray(np.asarray(req.tokens[:-1],
                                              np.int32)[None])
                _, dc = self.draft.prefill(toks, self._draft_total)
                req.draft_pos = len(req.tokens) - 1
                rows.append(dc)
            self._draft_caches = self._rows_concat(
                [self._draft_caches] + rows)

        # ---- merge admissions, then retire mid-stream finishers
        if not self.page_size:
            self._append_rows(pre_caches)
        self.inflight.extend(admitted)
        self._max_seen = max(self._max_seen, len(self.inflight))
        finished = [r for r in self.inflight if r.done]
        if finished:
            keep = [i for i, r in enumerate(self.inflight) if not r.done]
            self.inflight = [self.inflight[i] for i in keep]
            if not self.page_size:       # paged rows live in the pool
                self._drop_rows(keep)
            elif self.spec_depth:
                self._draft_caches = self._rows_keep(self._draft_caches,
                                                     keep)
            self._retire(finished)
        self.round += 1
        return bool(self.inflight or self.queue)

    # ------------------------------------------------------------------
    def run(self) -> Tuple[Dict[int, np.ndarray], ServeStats]:
        """Drain the queue; returns ({rid: full token sequence}, stats)."""
        t_start = time.perf_counter()
        while self.step():
            pass
        lat = time.perf_counter() - t_start
        outs = {rid: np.asarray(r.tokens)
                for rid, r in sorted(self.done.items())}
        expert_kw = {}
        if self.engine.expert is not None:
            expert_kw = self.engine.expert.stats_since(self._expert_snap)
            self._expert_snap = self.engine.expert.snapshot()
        paged_kw = {}
        if self.page_size:
            paged_kw = dict(
                page_size=self.page_size,
                pages_allocated=self.pool.stats.allocs,
                page_reuses=self.pool.stats.reuses,
                prefix_hit_pages=self.tree.hits if self.tree else 0,
                cow_copies=self.pool.stats.cow_copies,
                preemptions=self.preemptions,
                pool_pages_peak=self.pool.mapped_peak)
        spec_kw = {}
        if self.spec_depth:
            spec_kw = dict(spec_depth=self.spec_depth,
                           spec_rounds=self._spec_rounds,
                           draft_tokens=self._draft_tokens,
                           accepted_tokens=self._accepted_tokens)
        # paged mode: the pool records the true mapped high-water on
        # every alloc (an end-of-boundary sample would miss pages a
        # mid-loop preemption freed again)
        cache_peak = (self.pool.mapped_peak_bytes if self.page_size
                      else self._cache_peak)
        faults = _tele.counter_values("prefetch.retries",
                                      "prefetch.faults_absorbed")
        # every request retired: the request-scoped tiers must have
        # drained exactly (audit mode raises naming the leaking owner;
        # the pinned window / draft / expert reservation legitimately
        # stay resident for the session)
        self.ledger.audit_check_drained("stream", "kv_pages",
                                        "spec_headroom")
        stats = ServeStats(
            rounds=self.round, latency_s=lat, peak_bytes=self.ledger.peak,
            loads=sum(1 for e in self.events if e[1] == "load_end"),
            streamed_bytes=self.engine._streamed(self.events),
            new_tokens=sum(r.generated for r in self.done.values()),
            requests=len(self.done), max_inflight_seen=self._max_seen,
            cache_bytes_peak=cache_peak, events=self.events,
            seed=self.seed, **paged_kw, **expert_kw, **spec_kw,
            retries=faults[0] - self._fault_base[0],
            faults_absorbed=faults[1] - self._fault_base[1],
            peak_breakdown=dict(self.ledger.peak_breakdown),
            **self._slo_stats())
        self._record_metrics(stats)
        return outs, stats

    def _record_metrics(self, stats: ServeStats) -> None:
        """Publish the session's headline stats into the process-wide
        metrics registry, so ``snapshot()`` (serve.py's summary table and
        ``--metrics-out``) sees serving outcomes next to the live
        counters the subsystems incremented along the way."""
        m = _tele.metrics()
        m.gauge("serve.rounds").set(stats.rounds)
        m.gauge("serve.requests").set(stats.requests)
        m.gauge("serve.new_tokens").set(stats.new_tokens)
        m.gauge("serve.tokens_per_s").set(stats.tokens_per_s)
        m.gauge("serve.streamed_bytes").set(stats.streamed_bytes)
        m.gauge("serve.ledger_peak_bytes").set(stats.peak_bytes)
        m.gauge("serve.cache_peak_bytes").set(stats.cache_bytes_peak)
        # per-owner shares at the ledger peak (exported via --metrics-out;
        # they sum exactly to serve.ledger_peak_bytes)
        for owner, nbytes in stats.peak_breakdown.items():
            m.gauge(f"ledger.peak.{owner}_bytes").set(nbytes)
        if stats.expert_hits or stats.expert_misses:
            m.gauge("serve.expert_hit_rate").set(stats.expert_hit_rate)
        if stats.draft_tokens:
            m.gauge("serve.acceptance_rate").set(stats.acceptance_rate)
        if stats.page_size:
            m.gauge("serve.prefix_hit_pages").set(stats.prefix_hit_pages)

    # ---- serving-tier accounting -------------------------------------
    def _req_slo(self, req: Request
                 ) -> Tuple[Optional[float], Optional[float], bool]:
        """(ttft_rounds, tpot_rounds, meets_slo) for one finished
        request.  TTFT counts from the ORIGINAL arrival (born_round —
        preemption re-queues mutate arrival_round); TPOT averages the
        rounds per token after the first."""
        if req.rejected or req.first_token_round < 0:
            return None, None, False
        ttft = float(req.first_token_round - req.born_round + 1)
        tpot = (float(req.finished_round - req.first_token_round)
                / (req.generated - 1) if req.generated > 1 else 0.0)
        ok = True
        if self.slo is not None:
            if (self.slo.ttft_rounds is not None
                    and ttft > self.slo.ttft_rounds):
                ok = False
            if (self.slo.tpot_rounds is not None
                    and tpot > self.slo.tpot_rounds):
                ok = False
        return ttft, tpot, ok

    def _slo_stats(self) -> Dict:
        """Serving-tier ServeStats fields: round-based TTFT/TPOT
        percentiles (deterministic under a fixed trace), their
        wall-clock mirrors, and goodput-under-SLO."""
        reqs = list(self.done.values())
        ttfts, tpots, good_tokens, attained = [], [], 0, 0
        ttfts_s, tpots_s = [], []
        for r in reqs:
            ttft, tpot, ok = self._req_slo(r)
            if ttft is not None:
                ttfts.append(ttft)
                tpots.append(tpot)
                if r.t_first >= 0 and r.t_arrival >= 0:
                    ttfts_s.append(r.t_first - r.t_arrival)
                if r.generated > 1 and r.t_done >= 0 and r.t_first >= 0:
                    tpots_s.append((r.t_done - r.t_first)
                                   / (r.generated - 1))
            if ok:
                attained += 1
                good_tokens += r.generated

        def pct(xs, q):
            return float(np.percentile(np.asarray(xs), q)) if xs else 0.0

        # wall-clock latency histograms for the registry snapshot (the
        # drift report and --metrics-out read these)
        m = _tele.metrics()
        for v in ttfts_s:
            m.histogram("serve.ttft_s").observe(v)
        for v in tpots_s:
            m.histogram("serve.tpot_s").observe(v)

        return dict(
            tenants=len({r.tenant for r in reqs}) if reqs else 0,
            chunk_size=self.chunk,
            chunk_jobs=self._chunk_jobs,
            ttft_p50_rounds=pct(ttfts, 50),
            ttft_p99_rounds=pct(ttfts, 99),
            tpot_p50_rounds=pct(tpots, 50),
            tpot_p99_rounds=pct(tpots, 99),
            ttft_p50_s=pct(ttfts_s, 50), ttft_p99_s=pct(ttfts_s, 99),
            tpot_p50_s=pct(tpots_s, 50), tpot_p99_s=pct(tpots_s, 99),
            slo_attained=(attained / len(reqs)) if reqs else 1.0,
            goodput_tokens=good_tokens,
            slo_rejections=self.slo_rejections,
            policy=list(self.policy_log))

    # ------------------------------------------------------------------
    def warmup(self, prompt_lens=()) -> "BatchScheduler":
        """Pre-compile the serving executables: the batched decode fn for
        every batch size up to ``max_inflight`` (plus head/embed at those
        shapes) and the prefill fn per distinct prompt length — so the
        timed serving loop never stalls the Inference Agent on a jit
        compile while the Loading Agents race ahead."""
        eng = self.engine
        fns = eng.fns
        emb = eng._resident.get("embed") or eng._load("embed")
        head = eng._resident.get("head") or eng._load("head")
        w0 = eng._load(eng.layer_names[0])
        T = (self._nb * self.page_size if self.page_size
             else self.max_total_len)
        for s in sorted(set(int(p) for p in prompt_lens)):
            x = fns["embed"](emb, jnp.zeros((1, s), jnp.int32))
            px, _ = eng._layer_cache(0, w0, x, T)
            fns["head"](head, px).block_until_ready()
        x1 = fns["embed"](emb, jnp.zeros((1, 1), jnp.int32))
        _, c1 = eng._layer_cache(0, w0, x1, T)
        if self.page_size:
            # one fixed-size pool per leaf: compile the paged decode at
            # every batch size (the pool rows never change, so these are
            # the serving executables).  Speculative serving decodes
            # exclusively through W-wide verify windows, so it warms
            # those shapes instead — plus the draft's own executables.
            pool1 = self._pool_like(c1)
            w = self.spec_depth + 1
            for r in range(1, self.max_inflight + 1):
                tbr = jnp.zeros((r, self._nb), jnp.int32)
                if self.spec_depth:
                    xr = fns["embed"](emb, jnp.zeros((r, w), jnp.int32))
                    dr, _ = fns["layer_verify_paged"](
                        w0, xr, pool1, tbr, jnp.zeros((r,), jnp.int32))
                    fns["head_all"](head, dr).block_until_ready()
                else:
                    xr = fns["embed"](emb, jnp.zeros((r, 1), jnp.int32))
                    dr, _ = fns["layer_decode_paged"](
                        w0, xr, pool1, tbr, jnp.zeros((r,), jnp.int32))
                    fns["head"](head, dr).block_until_ready()
                if self.chunk:
                    # chunked prefill rides (r, C) verify windows
                    xc = fns["embed"](emb,
                                      jnp.zeros((r, self.chunk), jnp.int32))
                    dc, _ = fns["layer_verify_paged"](
                        w0, xc, pool1, tbr, jnp.zeros((r,), jnp.int32))
                    fns["head"](head, dc).block_until_ready()
            if self.chunk:
                # chunk rounds write straight into the pools — create
                # them now so a cold chunked admission needs no extra
                # layer load (see _ensure_chunk_pools)
                self._ensure_pool_arrays(
                    {name: c1 for name in eng.layer_names})
            if self.spec_depth:
                for s in sorted(set(int(p) for p in prompt_lens)):
                    self.draft.prefill(jnp.zeros((1, s), jnp.int32),
                                       self._draft_total)
                _, dc1 = self.draft.prefill(jnp.zeros((1, 1), jnp.int32),
                                            self._draft_total)
                for r in range(1, self.max_inflight + 1):
                    dcr = {name: jax.tree.map(
                        lambda a: jnp.concatenate([a] * r), c)
                        for name, c in dc1.items()}
                    self.draft.decode_batch(
                        jnp.zeros((r, 1), jnp.int32), dcr,
                        jnp.zeros((r,), jnp.int32))
        else:
            for r in range(1, self.max_inflight + 1):
                cr = jax.tree.map(lambda a: jnp.concatenate([a] * r), c1)
                xr = fns["embed"](emb, jnp.zeros((r, 1), jnp.int32))
                dr, _ = eng._layer_decode(0, w0, xr, cr,
                                          jnp.zeros((r,), jnp.int32))
                fns["head"](head, dr).block_until_ready()
        del w0, emb, head
        if eng.expert is not None:
            # warmup's compile-time fetches are not serving traffic
            self._expert_snap = eng.expert.snapshot()
        self._t0 = time.perf_counter()
        return self
