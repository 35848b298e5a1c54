"""Hermes framework facade (paper §IV): Layer Profiler -> Pipeline Planner
-> Execution Engine, wired together.

    hermes = Hermes(ckpt_dir, cfg)
    profile = hermes.profile()                  # §IV-1
    schedule = hermes.plan([b1, b2, None])      # §IV-2
    logits, stats = hermes.execute(tokens, budget_bytes=b1)   # §IV-3

Generation workloads get the generation-aware tier:

    gplan = hermes.plan_generate([b1], prompt_len=128, new_tokens=32)[0]
    stats = hermes.execute(tokens, generate=32, kv_cache=True,
                           budget_bytes=b1)     # picks (m, pin) jointly

Quantized weight streaming threads through the same facade:

    h8 = hermes.quantized("int8")      # sibling int8 checkpoint (cached)
    g = hermes.plan_generate([b1], quants=("fp32", "int8", "int4"),
                             prompt_len=128, new_tokens=32)[0]
    engine = hermes.quantized(g.dtype).engine(...)   # g.dtype = winner
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict, List, Optional, Sequence

from repro.checkpoint.partition import ensure_quantized, load_manifest
from repro.core.engine import DraftModel, PipeloadEngine, RunStats
from repro.core.planner import GenPlanEntry, PlanEntry, plan, plan_generate
from repro.core.profiler import load_profile, profile_model, save_profile
from repro.kernels.autotune import device_arch
from repro.models.config import ModelConfig

# planner label for "no quantization: stream shards at the ckpt dtype"
FP_LABEL = "fp32"


class Hermes:
    def __init__(self, ckpt_dir, cfg: ModelConfig):
        self.dir = Path(ckpt_dir)
        self.cfg = cfg
        self._profile: Optional[Dict] = None
        self._variants: Dict[str, "Hermes"] = {}

    # ---- Telemetry (core/telemetry.py) ---------------------------------
    def telemetry(self):
        """The process-wide telemetry handle: ``.enable()`` turns on span
        tracing across every PIPELOAD subsystem, ``.metrics`` is the
        always-on registry, ``.export_chrome_trace(path)`` writes a
        Perfetto-loadable timeline of the runs since enable()."""
        from repro.core.telemetry import telemetry
        return telemetry()

    # ---- Layer Profiler ------------------------------------------------
    def profile(self, *, batch: int = 1, seq: int = 128,
                force: bool = False) -> Dict:
        """Measured per-shard profile, cached in the checkpoint directory
        under the measuring device's kind (``profile.<arch>.json``): a
        profile taken on one device never plans a run on another.  A
        cached profile whose shard ``format`` is not the manifest's was
        measured on another store's reads, and is measured again."""
        cache = self.dir / f"profile.{device_arch()}.json"
        if not force and self._profile is not None:
            return self._profile
        if not force and cache.exists():
            kept = load_profile(cache)
            if kept.get("format") == load_manifest(self.dir)["format"]:
                self._profile = kept
                return self._profile
        self._profile = profile_model(self.dir, self.cfg, batch=batch,
                                      seq=seq)
        save_profile(self._profile, cache)
        return self._profile

    # ---- Kernel autotune (kernels/autotune.py) -------------------------
    def autotune(self, *, page_size: Optional[int] = None,
                 quant: Optional[str] = None, tokens: int = 256,
                 force: bool = False, cache_path=None) -> Dict:
        """Per-device kernel tile / impl selection, seeded by this
        checkpoint's Layer Profiler run and cached to disk (repeat runs
        skip the timing sweep).  Applies the winners as the jitted
        kernel wrappers' process-wide defaults and returns them."""
        from repro.kernels.autotune import tune_for_model
        host = self.quantized(quant) if quant else self
        return tune_for_model(self.cfg, host.profile(),
                              page_size=page_size, quant=quant,
                              tokens=tokens, force=force,
                              cache_path=cache_path)

    # ---- Quantized checkpoint variants ---------------------------------
    def quantized(self, quant: Optional[str]) -> "Hermes":
        """Hermes over the ``quant`` variant of this checkpoint.  The
        sibling directory ``<dir>-<quant>`` is transcoded once (no model
        init) and reused — including its own cached per-device profile — and
        re-transcoded automatically if the source checkpoint changed
        underneath it (``checkpoint.ensure_quantized``)."""
        if quant in (None, FP_LABEL):
            return self
        if quant not in self._variants:
            dst = self.dir.parent / f"{self.dir.name}-{quant}"
            ensure_quantized(self.dir, dst, quant)
            self._variants[quant] = Hermes(dst, self.cfg)
        return self._variants[quant]

    def _quant_profiles(self, quants: Sequence[Optional[str]],
                        **profile_kw) -> Dict[str, Dict]:
        """One Layer Profiler run per requested shard dtype."""
        labels = [q or FP_LABEL for q in quants]
        return {lb: self.quantized(lb).profile(**profile_kw)
                for lb in labels}

    # ---- Pipeline Planner ----------------------------------------------
    def plan(self, budgets: List[Optional[int]],
             max_agents: Optional[int] = None,
             quants: Optional[Sequence[Optional[str]]] = None
             ) -> List[PlanEntry]:
        """Schedule per budget; ``quants`` (e.g. ``("fp32", "int8")``)
        widens the search over shard dtype — the winning entry's
        ``dtype`` says which variant to execute."""
        prof = (self.profile() if quants is None
                else self._quant_profiles(quants))
        return plan(prof, budgets, max_agents)

    def best_agents(self, budget_bytes: Optional[int]) -> int:
        return self.plan([budget_bytes])[0].num_agents

    def plan_generate(self, budgets: List[Optional[int]], *,
                      batch: int = 1, prompt_len: int = 128,
                      new_tokens: int = 32,
                      max_agents: Optional[int] = None,
                      max_pin: Optional[int] = None,
                      max_inflight: int = 1,
                      quants: Optional[Sequence[Optional[str]]] = None,
                      page_sizes: Sequence[int] = (),
                      shared_prefix_len: int = 0,
                      spec_depths: Sequence[int] = (),
                      spec_draft: Optional[Dict] = None,
                      slo_ttft_s: Optional[float] = None,
                      slo_tpot_s: Optional[float] = None,
                      chunk_prefill: int = 0
                      ) -> List[GenPlanEntry]:
        """Generation-aware schedule: joint (num_agents, pin_window) with
        KV-cache bytes charged against the budget.  ``max_inflight > 1``
        additionally searches the continuous-batching in-flight count
        (capacity-first; see ``planner.plan_generate``); ``quants``
        widens the search over shard dtype (KV pages keep the model
        dtype, so ``cache_bytes_per_layer`` is shared); ``page_sizes``
        widens it over PAGED KV reservations (core/kv_pages.py) —
        ``shared_prefix_len`` tells the model how many leading prompt
        tokens the workload's requests share, whose full pages are
        charged once across the batch; ``spec_depths`` + ``spec_draft``
        widen it over SPECULATIVE verify depths (a pinned draft's bytes,
        cache row and acceptance rate — see ``planner.plan_generate``);
        ``slo_ttft_s``/``slo_tpot_s`` gate the capacity-first search on
        predicted TTFT/TPOT (``chunk_prefill`` models chunk-joined
        prefill rounds — see the planner's SLO dimension)."""
        cb = self.cfg.cache_bytes(batch, prompt_len + new_tokens)
        prof = (self.profile() if quants is None
                else self._quant_profiles(quants, batch=1, seq=prompt_len))
        return plan_generate(prof, budgets, new_tokens=new_tokens,
                             cache_bytes_per_layer=cb, max_agents=max_agents,
                             max_pin=max_pin, max_inflight=max_inflight,
                             page_sizes=tuple(page_sizes),
                             total_len=prompt_len + new_tokens,
                             shared_prefix_len=shared_prefix_len,
                             spec_depths=tuple(spec_depths),
                             spec_draft=spec_draft,
                             slo_ttft_s=slo_ttft_s, slo_tpot_s=slo_tpot_s,
                             chunk_prefill=chunk_prefill)

    # ---- Execution Engine ----------------------------------------------
    def engine(self, *, mode: str = "pipeload",
               budget_bytes: Optional[int] = None,
               num_agents: Optional[int] = None,
               pin_window: int = 0,
               expert_cache_bytes: Optional[int] = None,
               page_size: Optional[int] = None) -> PipeloadEngine:
        if num_agents is None and mode == "pipeload":
            num_agents = self.best_agents(budget_bytes)
        return PipeloadEngine(self.dir, self.cfg, mode=mode,
                              num_agents=num_agents or 1,
                              budget_bytes=budget_bytes,
                              pin_window=pin_window,
                              expert_cache_bytes=expert_cache_bytes,
                              page_size=page_size)

    def scheduler(self, *, budget_bytes: Optional[int] = None,
                  max_inflight: int = 4, prompt_len: int = 128,
                  new_tokens: int = 32,
                  num_agents: Optional[int] = None,
                  pin_window: Optional[int] = None,
                  max_total_len: Optional[int] = None,
                  quants: Optional[Sequence[Optional[str]]] = None,
                  page_sizes: Sequence[int] = (),
                  shared_prefix_len: int = 0,
                  prefix_cache: bool = True,
                  seed: Optional[int] = None,
                  draft: Optional["DraftModel"] = None,
                  spec_depth: Optional[int] = None,
                  draft_acceptance: float = 0.8,
                  autotune: bool = False,
                  chunk_prefill: int = 0,
                  slo: Optional["SLO"] = None,
                  slo_ttft_s: Optional[float] = None,
                  slo_tpot_s: Optional[float] = None
                  ) -> "BatchScheduler":
        """Continuous-batching serving facade: plan the
        (num_agents, pin_window, inflight) triple for the budget, build
        the engine, and wrap it in a ``BatchScheduler`` ready for
        ``submit()``/``run()``.  ``prompt_len``/``new_tokens`` describe
        the TYPICAL request (they size the padded cache reservation);
        per-request lengths may vary below ``max_total_len``.
        ``quants`` widens the plan over shard dtype and ``page_sizes``
        over paged KV reservations (``shared_prefix_len`` models the
        workload's common prompt prefix); the engine is built on the
        winning checkpoint variant with the winning page size.  A
        ``draft`` model adds the SPECULATIVE dimension: ``spec_depth``
        fixes the verify depth (None = search {1, 2, 4} jointly at the
        modelled ``draft_acceptance``), and the winning depth — 0 when
        speculation does not pay at this budget — drives the
        scheduler's draft-and-verify rounds.

        The SERVING-TIER knobs: ``chunk_prefill`` (tokens per prefill
        chunk; needs ``page_sizes``, incompatible with ``draft``) joins
        long prompts into decode rounds; ``slo`` (a rounds-based
        ``scheduler.SLO``) arms admission-time shedding; and
        ``slo_ttft_s``/``slo_tpot_s`` gate the planner's capacity-first
        search — when only the seconds targets are given, the winning
        schedule's predicted round latency converts them into the
        rounds-based ``SLO`` handed to the scheduler."""
        from repro.core.scheduler import SLO, BatchScheduler
        if chunk_prefill and draft is not None:
            raise ValueError("chunk_prefill is incompatible with a draft "
                             "model (speculative rounds own the verify "
                             "window)")
        if chunk_prefill and not page_sizes:
            raise ValueError("chunk_prefill requires page_sizes (chunk "
                             "rounds write through the paged KV kernel)")
        spec_kw = {}
        if draft is not None:
            depths = ((spec_depth,) if spec_depth else (1, 2, 4))
            total = max_total_len or prompt_len + new_tokens
            spec_kw = dict(
                spec_depths=tuple(d for d in depths if d and d > 0),
                spec_draft=dict(
                    bytes=draft.total_bytes,
                    cache_bytes=draft.cache_bytes(1, total + max(depths)),
                    acceptance=draft_acceptance))
        g = self.plan_generate([budget_bytes], prompt_len=prompt_len,
                               new_tokens=new_tokens,
                               max_inflight=max_inflight, quants=quants,
                               page_sizes=page_sizes,
                               # sharing off -> every page is private;
                               # the plan must not assume prefix hits
                               shared_prefix_len=(shared_prefix_len
                                                  if prefix_cache
                                                  else 0),
                               slo_ttft_s=slo_ttft_s,
                               slo_tpot_s=slo_tpot_s,
                               chunk_prefill=chunk_prefill,
                               **spec_kw)[0]
        if not g.feasible:
            raise ValueError(
                f"no feasible serving schedule for budget {budget_bytes}: "
                f"best candidate predicts peak {g.predicted_peak_bytes} "
                f"bytes ({g.cache_bytes} of KV cache at inflight="
                f"{g.inflight}); raise the budget or shrink "
                f"prompt/new_tokens")
        host = self.quantized(g.dtype) if quants is not None else self
        if autotune:
            # tune AFTER planning: the planner's winning (dtype,
            # page_size) pair keys the autotune cache lookup, so the
            # kernels are tuned for the configuration that will serve
            self.autotune(page_size=(g.page_size or None),
                          quant=(g.dtype if quants is not None
                                 and g.dtype != FP_LABEL else None))
        eng = host.engine(mode="pipeload", budget_bytes=budget_bytes,
                          num_agents=(num_agents if num_agents is not None
                                      else g.num_agents),
                          pin_window=(pin_window if pin_window is not None
                                      else g.pin_window),
                          expert_cache_bytes=(g.expert_cache_bytes or None),
                          page_size=(g.page_size or None))
        if slo is None and (slo_ttft_s or slo_tpot_s):
            # convert the seconds targets into the scheduler's rounds
            # clock via the winning schedule's predicted round latency
            rl = g.predicted_per_token_s
            if rl and rl > 0:
                slo = SLO(
                    ttft_rounds=(max(int(slo_ttft_s / rl), 1)
                                 if slo_ttft_s else None),
                    tpot_rounds=((slo_tpot_s / rl)
                                 if slo_tpot_s else None))
        return BatchScheduler(eng, max_inflight=g.inflight,
                              max_total_len=(max_total_len
                                             or prompt_len + new_tokens),
                              prefix_cache=prefix_cache, seed=seed,
                              draft=(draft if g.spec_depth else None),
                              spec_depth=g.spec_depth,
                              chunk_prefill=(chunk_prefill
                                             if g.page_size else 0),
                              slo=slo)

    def execute(self, tokens, *, generate: int = 0, mode: str = "pipeload",
                budget_bytes: Optional[int] = None,
                num_agents: Optional[int] = None,
                pin_window: Optional[int] = None,
                kv_cache: bool = False) -> RunStats:
        expert_cache = None
        if (kv_cache and generate and mode == "pipeload"
                and (num_agents is None or pin_window is None)):
            # generation-aware tier picks (num_agents, pin_window) jointly
            b, s0 = tokens.shape
            g = self.plan_generate([budget_bytes], batch=b, prompt_len=s0,
                                   new_tokens=generate)[0]
            if not g.feasible:
                raise ValueError(
                    f"no feasible generation schedule for budget "
                    f"{budget_bytes}: best candidate predicts peak "
                    f"{g.predicted_peak_bytes} bytes ({g.cache_bytes} of "
                    f"KV cache); raise the budget or shrink "
                    f"batch/prompt/new_tokens")
            num_agents = g.num_agents if num_agents is None else num_agents
            pin_window = g.pin_window if pin_window is None else pin_window
            expert_cache = g.expert_cache_bytes or None
        eng = self.engine(mode=mode, budget_bytes=budget_bytes,
                          num_agents=num_agents,
                          pin_window=pin_window or 0,
                          expert_cache_bytes=expert_cache)
        if generate:
            _, stats = eng.run_generate(tokens, generate, kv_cache=kv_cache)
        else:
            _, stats = eng.run_single(tokens)
        return stats
