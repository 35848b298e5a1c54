"""Serving launcher: continuous-batching PIPELOAD inference.

    PYTHONPATH=src python -m repro.launch.serve --arch gpt2-base \
        --budget-mb 600 --requests 8 --max-inflight 4 --new-tokens 8

Builds (or reuses) a layer-partitioned checkpoint, profiles it, lets the
generation-aware Pipeline Planner pick the ``(num_agents, pin_window,
inflight)`` triple for the memory budget, and serves the requests through
the continuous-batching scheduler (core/scheduler.py): each PIPELOAD
round streams every layer ONCE and applies it to all in-flight requests,
so aggregate tokens/s scales with concurrency while peak memory stays
within the budget.

``--arrival-rate R`` replays a Poisson arrival process (R requests per
round on average, deterministic under ``--seed`` — the seed is recorded
in ``ServeStats.seed`` so any serve-level run can be replayed exactly)
instead of an everyone-at-once burst; ``--no-kv-cache`` falls back to
the paper's sequential per-token re-prefill engine (§V-B2) for
comparison.

``--shared-prefix N`` makes every request's first N prompt tokens
identical (the shared-system-prompt trace), and ``--page-size P`` adds
the PAGED KV reservation (core/kv_pages.py) to the planner's search:
requests map fixed-size cache pages through block tables, the radix
prefix tree maps the shared prompt's pages once across the fleet, and
admission charges pages actually mapped instead of
``inflight x max_total_len`` — more concurrent users under the same
budget.  ``--no-prefix-cache`` disables the sharing (pages stay
per-request) for A/B runs.

``--quant int8|int4`` serves per-channel-quantized shards (~4x/8x fewer
bytes streamed and resident per layer — deeper pin windows and more
in-flight requests under the same budget); ``--quant auto`` profiles
every dtype and lets the planner pick shard precision jointly with
``(num_agents, pin_window, inflight)``.

``--draft-arch`` turns on SPECULATIVE serving (needs ``--page-size``): a
small draft model — pinned whole under the budget, like the pin window —
proposes ``--spec-depth`` tokens per request per round, the target
scores each request's whole window in ONE stacked verify round over the
paged KV block tables, and the accepted prefix plus the target's bonus
token commit together, so a round advances each request by up to
``depth + 1`` tokens for one weight stream.  The draft must share the
target's vocabulary; greedy outputs are token-identical to
non-speculative serving.  ``--spec-depth 0`` (the default with a draft)
lets the planner search depth jointly with the rest of the schedule.

The SERVING TIER (multi-tenant SLO mode): ``--tenants N`` replays the
seeded heavy-tailed multi-tenant trace from ``repro.data.traces`` —
Zipf tenant mix, lognormal prompt lengths, priority classes 0..2 whose
high-priority arrivals may PREEMPT the lowest-priority/youngest
in-flight request — and ``--trace FILE`` replays a saved JSON trace
verbatim (``repro.data.traces.save_trace``) for exact cross-machine
reproduction.  ``--chunk-prefill C`` (needs ``--page-size``) splits
prompts longer than C into page-aligned chunks joined into decode
rounds, so a long prompt no longer stalls every in-flight decode for a
full weight stream; outputs stay token-identical.  ``--slo-ttft-ms`` /
``--slo-tpot-ms`` feed the planner's SLO gate (capacity-first search
stops at the largest in-flight count predicted to MEET the targets)
and arm the scheduler's rounds-based SLO accounting — the summary
reports p50/p99 TTFT/TPOT and goodput-under-SLO; ``--slo-shed``
additionally rejects requests at admission once their best-case TTFT
is already blown.

MoE architectures (e.g. ``--arch qwen3_moe_30b_a3b``) are partitioned
expert-split and served through the expert-streaming subsystem
(core/expert_stream.py): attention+router shards stream eagerly, the
round's activated experts are demand-loaded after the router runs, and
the planner sizes the ExpertCache jointly with the rest of the
schedule.  The summary line reports the expert hit rate and per-round
unique-expert count.
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time
from pathlib import Path

import jax
import numpy as np

from repro import compile_cache
from repro.analysis.report import (drift_report, format_drift,
                                   format_peak_breakdown,
                                   peak_breakdown_report)
from repro.checkpoint import ensure_partitioned
from repro.configs import get, names
from repro.core import SLO, BatchScheduler, Hermes
from repro.core import telemetry as tele
from repro.data.traces import (load_trace, make_trace, submit_trace,
                               trace_max_len)
from repro.models.api import build_model

CKPT_ROOT = Path(tempfile.gettempdir()) / "repro_ckpts"
QUANT_CHOICES = ("fp32", "int8", "int4", "auto")


def init_params(cfg, seed: int = 0):
    """The model's random weights from ``seed``, made on the host CPU
    where that backend is up: a model larger than the device's budget
    must never have to sit on the device whole, and the same seed gives
    the same weights to every caller (the checkpoint and a reference)."""
    try:
        host = jax.local_devices(backend="cpu")[0]
    except RuntimeError:           # JAX_PLATFORMS left the CPU backend out
        host = None
    with jax.default_device(host):
        return build_model(cfg).init(jax.random.PRNGKey(seed))


def ensure_checkpoint(cfg, seed: int = 0) -> Path:
    return ensure_partitioned(CKPT_ROOT / cfg.name.replace("/", "_"), cfg,
                              lambda: init_params(cfg, seed))


def poisson_arrivals(n: int, rate: float | None,
                     rng: np.random.Generator) -> list[int]:
    """Arrival round per request: a Poisson process at ``rate`` requests
    per ROUND (rounds are the scheduler's clock, so the trace replays
    identically on any machine).  ``rate=None``/0 = all arrive at once."""
    if not rate:
        return [0] * n
    gaps = rng.exponential(1.0 / rate, size=n)
    return np.floor(np.cumsum(gaps)).astype(int).tolist()


def export_telemetry(trace_out: str | None, metrics_out: str | None):
    """Write the run's Chrome trace / metrics-registry snapshot, if the
    caller asked for them."""
    if trace_out:
        tele.export_chrome_trace(trace_out)
        print(f"trace: wrote {trace_out} (load it in ui.perfetto.dev "
              "or chrome://tracing)")
    if metrics_out:
        Path(metrics_out).write_text(
            json.dumps(tele.metrics().snapshot(), indent=1))
        print(f"metrics: wrote {metrics_out}")


def run(arch: str, *, budget_mb: float | None = None, requests: int = 4,
        prompt_len: int = 16, new_tokens: int = 8, reduced: bool = True,
        num_agents: int | None = None, pin_window: int | None = None,
        kv_cache: bool = True, max_inflight: int = 4,
        arrival_rate: float | None = None, seed: int = 0,
        quant: str = "fp32", page_size: int = 0,
        prefix_cache: bool = True, shared_prefix: int = 0,
        draft_arch: str | None = None, spec_depth: int = 0,
        autotune: bool = False, trace: str | None = None,
        tenants: int = 0, chunk_prefill: int = 0,
        slo_ttft_ms: float | None = None, slo_tpot_ms: float | None = None,
        slo_shed: bool = False, trace_out: str | None = None,
        metrics_out: str | None = None):
    assert quant in QUANT_CHOICES, quant
    compile_cache.enable()
    # fresh telemetry per run: zero the registry IN PLACE (cached
    # instruments stay wired) and install a recording tracer only when a
    # timeline export was requested — tracing off costs nothing
    tele.metrics().reset()
    if trace_out:
        tele.enable()
    cfg = get(arch)
    if reduced:
        cfg = cfg.reduced().with_(num_layers=8)
    if chunk_prefill and not page_size:
        raise SystemExit("error: --chunk-prefill needs --page-size "
                         "(chunk rounds write through the paged KV "
                         "kernel)")
    if chunk_prefill and draft_arch:
        raise SystemExit("error: --chunk-prefill is incompatible with "
                         "--draft-arch (speculative rounds own the "
                         "verify window)")
    if (trace or tenants) and not kv_cache:
        raise SystemExit("error: --trace/--tenants need the KV-cache "
                         "scheduler; drop --no-kv-cache")
    ckpt = ensure_checkpoint(cfg)
    hermes = Hermes(ckpt, cfg)
    draft = None
    if draft_arch:
        if not page_size:
            raise SystemExit("error: --draft-arch needs --page-size "
                             "(the verify window rides the paged KV "
                             "block tables)")
        if not kv_cache:
            raise SystemExit("error: --draft-arch needs the KV-cache "
                             "scheduler; drop --no-kv-cache")
        dcfg = get(draft_arch)
        if reduced:
            dcfg = dcfg.reduced()       # 2 layers: a genuinely small draft
        dcfg = dcfg.with_(name=dcfg.name + "-draft")
        if dcfg.vocab_size != cfg.vocab_size:
            raise SystemExit(
                f"error: draft vocab ({dcfg.vocab_size}) must match the "
                f"target's ({cfg.vocab_size}) — proposals are target "
                f"token ids")
        from repro.core.engine import DraftModel
        draft = DraftModel(ensure_checkpoint(dcfg), dcfg)
    # fixed dtype = a one-entry search; "auto" lets the planner pick the
    # shard precision jointly with the schedule
    quants = ("fp32", "int8", "int4") if quant == "auto" else (quant,)
    budget = int(budget_mb * 2**20) if budget_mb else None
    rng = np.random.default_rng(seed)
    shared_prefix = max(0, min(shared_prefix, prompt_len))
    prompts = rng.integers(0, cfg.vocab_size, (requests, prompt_len))
    if shared_prefix:
        # shared-system-prompt trace: every request opens with the same
        # tokens (what the prefix tree maps once across the fleet)
        prompts[:, :shared_prefix] = prompts[0, :shared_prefix]
    serve_trace = None
    if trace:
        serve_trace = load_trace(trace)
    elif tenants:
        # seeded heavy-tailed multi-tenant mix: prompt_len/new_tokens
        # bound the distributions so the planned reservation still fits
        serve_trace = make_trace(
            requests, tenants=tenants, seed=seed, vocab=cfg.vocab_size,
            arrival_rate=arrival_rate or 1.0,
            prompt_mean=max(prompt_len // 2, 4), max_prompt=prompt_len,
            new_mean=max(new_tokens // 2, 1), max_new=new_tokens,
            prefix_len=shared_prefix, share_prefix=0.6)
    total_len = prompt_len + new_tokens
    if serve_trace:
        total_len = max(total_len, trace_max_len(serve_trace))

    if not kv_cache:
        # paper's engine (§V-B2): sequential re-prefill, one weight
        # stream per request per token — the baseline the scheduler beats
        plan = hermes.plan([budget], quants=quants)[0]
        hermes = hermes.quantized(plan.dtype)
        agents, pin = num_agents or plan.num_agents, pin_window or 0
        print(f"planner: budget={budget_mb}MB -> {agents} agents, "
              f"dtype={plan.dtype}, "
              f"predicted latency {plan.predicted_latency_s*1e3:.0f}ms, "
              f"peak {plan.predicted_peak_bytes/2**20:.0f}MB")
        with hermes.engine(mode="pipeload", budget_bytes=budget,
                           num_agents=agents, pin_window=pin) as eng:
            eng.warmup(requests, prompt_len)
            t0 = time.time()
            out, stats = eng.run_generate(prompts, new_tokens,
                                          kv_cache=False)
            dt = time.time() - t0
        print(f"served {requests} reqs x {new_tokens} tokens in {dt:.2f}s "
              f"({requests*new_tokens/dt:.1f} tok/s), "
              f"peak {stats.peak_bytes/2**20:.0f}MB, "
              f"{stats.loads} shard loads "
              f"({stats.streamed_bytes/2**20:.0f}MB streamed)")
        if stats.retries or stats.faults_absorbed:
            print(f"  prefetch faults: {stats.retries} retries, "
                  f"{stats.faults_absorbed} loads recovered")
        print(format_peak_breakdown(peak_breakdown_report(stats)))
        export_telemetry(trace_out, metrics_out)
        return out, stats

    spec_kw = {}
    if draft is not None:
        depths = (spec_depth,) if spec_depth else (1, 2, 4)
        total = prompt_len + new_tokens
        spec_kw = dict(
            spec_depths=tuple(d for d in depths if d and d > 0),
            spec_draft=dict(bytes=draft.total_bytes,
                            cache_bytes=draft.cache_bytes(
                                1, total + max(depths)),
                            acceptance=0.8))
    g = hermes.plan_generate([budget], prompt_len=prompt_len,
                             new_tokens=new_tokens,
                             max_inflight=max_inflight,
                             quants=quants,
                             page_sizes=(page_size,) if page_size else (),
                             # with sharing disabled every page is
                             # private — don't let the plan assume hits
                             shared_prefix_len=(shared_prefix
                                                if prefix_cache else 0),
                             slo_ttft_s=(slo_ttft_ms / 1e3
                                         if slo_ttft_ms else None),
                             slo_tpot_s=(slo_tpot_ms / 1e3
                                         if slo_tpot_ms else None),
                             chunk_prefill=chunk_prefill,
                             **spec_kw)[0]
    if not g.feasible:
        raise SystemExit(
            f"error: no feasible serving schedule for budget="
            f"{budget_mb}MB (best candidate predicts peak "
            f"{g.predicted_peak_bytes/2**20:.1f}MB, of which "
            f"{g.cache_bytes/2**20:.1f}MB KV cache at inflight="
            f"{g.inflight}); raise the budget, shrink "
            f"prompt/new-tokens, or pass --no-kv-cache")
    hermes = hermes.quantized(g.dtype)
    agents = num_agents or g.num_agents
    pin = g.pin_window if pin_window is None else pin_window
    # an explicit --spec-depth pins the verify depth; 0 defers to the
    # planner's joint pick (which may conclude speculation doesn't pay)
    depth = (spec_depth or g.spec_depth) if draft is not None else 0
    print(f"planner(serve): budget={budget_mb}MB -> {agents} agents, "
          f"pin={pin}, inflight={g.inflight}, dtype={g.dtype}, predicted "
          f"{g.predicted_throughput_tps:.1f} tok/s aggregate, peak "
          f"{g.predicted_peak_bytes/2**20:.0f}MB "
          f"(cache {g.cache_bytes/2**20:.1f}MB"
          + (f", page size {g.page_size}" if g.page_size else "")
          + (f", spec depth {depth}" if depth else "")
          + (f", chunk {chunk_prefill}" if chunk_prefill else "")
          + (f", expert cache {g.expert_cache_bytes/2**20:.1f}MB"
             if g.expert_cache_bytes else "") + ")")
    if (slo_ttft_ms or slo_tpot_ms):
        print(f"planner(slo): predicted ttft {g.predicted_ttft_s*1e3:.0f}ms"
              f" / tpot {g.predicted_tpot_s*1e3:.1f}ms -> "
              f"{'MEETS' if g.slo_ok else 'MISSES'} the target"
              + ("" if g.slo_ok else " (serving degraded: no feasible "
                 "schedule attains it)"))

    if autotune:
        # per-device kernel tiles for the planner's winning (dtype, page
        # size), seeded from this checkpoint's profile and cached to
        # disk — repeat serves skip the timing sweep
        sel = hermes.autotune(page_size=g.page_size or None,
                              quant=(g.dtype if g.dtype != "fp32"
                                     else None))
        mm = sel["matmul"]
        print(f"autotune({sel['arch']}): matmul tiles "
              f"{mm['block_m']}x{mm['block_n']}x{mm['block_k']}"
              + (f", paged impl {sel['paged_decode']['impl']}"
                 if "paged_decode" in sel else ""))
    eng = hermes.engine(mode="pipeload", budget_bytes=budget,
                        num_agents=agents, pin_window=pin,
                        expert_cache_bytes=g.expert_cache_bytes or None,
                        page_size=g.page_size or None)
    slo = None
    if slo_ttft_ms or slo_tpot_ms:
        # seconds targets -> the scheduler's deterministic rounds clock,
        # via the planned round latency (same conversion as the facade)
        rl = g.predicted_per_token_s
        if rl and rl > 0 and np.isfinite(rl):
            slo = SLO(ttft_rounds=(max(int(slo_ttft_ms / 1e3 / rl), 1)
                                   if slo_ttft_ms else None),
                      tpot_rounds=((slo_tpot_ms / 1e3 / rl)
                                   if slo_tpot_ms else None),
                      shed=slo_shed)
    sched = BatchScheduler(eng, max_inflight=g.inflight,
                           max_total_len=total_len,
                           prefix_cache=prefix_cache, seed=seed,
                           draft=(draft if depth else None),
                           spec_depth=depth,
                           chunk_prefill=(chunk_prefill
                                          if g.page_size else 0),
                           slo=slo)
    try:
        sched.warmup(prompt_lens=[prompt_len])
        if serve_trace is not None:
            submit_trace(sched, serve_trace)
        else:
            arrivals = poisson_arrivals(requests, arrival_rate, rng)
            for i in range(requests):
                sched.submit(prompts[i], new_tokens,
                             arrival_round=arrivals[i])
        t0 = time.time()
        outs, stats = sched.run()
        dt = time.time() - t0
    except BaseException:
        sched.close()
        raise
    print(f"served {stats.requests} reqs x {new_tokens} tokens in "
          f"{stats.rounds} rounds / {dt:.2f}s "
          f"({stats.tokens_per_s:.1f} tok/s aggregate), peak "
          f"{stats.peak_bytes/2**20:.0f}MB "
          f"(cache {stats.cache_bytes_peak/2**20:.1f}MB), "
          f"{stats.loads} shard loads "
          f"({stats.streamed_bytes/2**20:.0f}MB streamed), "
          f"max inflight seen {stats.max_inflight_seen}, "
          f"seed {stats.seed}")
    # end-of-run summary: ONE metrics-snapshot table (stats stays the
    # source of truth for the numbers) instead of per-subsystem prints
    rows: dict[str, object] = {
        "streamed_mb": f"{stats.streamed_bytes/2**20:.0f}",
        "ledger_peak_mb": (f"{stats.peak_bytes/2**20:.0f}"
                           + (f" / budget {budget_mb:.0f}"
                              if budget_mb else "")),
        "cache_peak_mb": f"{stats.cache_bytes_peak/2**20:.1f}",
        "shard_loads": stats.loads,
    }
    if stats.retries or stats.faults_absorbed:
        rows["prefetch_retries"] = stats.retries
        rows["faults_absorbed"] = stats.faults_absorbed
    if stats.page_size:
        rows["page_size"] = stats.page_size
        rows["page_allocs"] = (f"{stats.pages_allocated} "
                               f"({stats.page_reuses} from the free "
                               f"list, pool peak {stats.pool_pages_peak})")
        rows["prefix_hit_pages"] = stats.prefix_hit_pages
        rows["cow_copies"] = stats.cow_copies
        rows["preemptions"] = stats.preemptions
    if stats.chunk_size:
        rows["chunk_prefill"] = (f"{stats.chunk_size}-token chunks, "
                                 f"{stats.chunk_jobs} jobs joined into "
                                 "decode rounds")
    if serve_trace is not None or slo is not None:
        rows["ttft_p50_p99_rounds"] = (f"{stats.ttft_p50_rounds:.1f} / "
                                       f"{stats.ttft_p99_rounds:.1f}")
        rows["tpot_p50_p99_rounds"] = (f"{stats.tpot_p50_rounds:.2f} / "
                                       f"{stats.tpot_p99_rounds:.2f}")
        rows["slo_attained"] = f"{stats.slo_attained:.0%}"
        rows["goodput_tokens"] = (f"{stats.goodput_tokens} "
                                  f"({stats.goodput_tokens_per_s:.1f} "
                                  "tok/s)")
        rows["shed"] = stats.slo_rejections
        rows["tenants"] = stats.tenants
    if stats.spec_depth:
        rows["spec_depth"] = stats.spec_depth
        rows["spec_accepted"] = (f"{stats.accepted_tokens}/"
                                 f"{stats.draft_tokens} "
                                 f"({stats.acceptance_rate:.0%}) over "
                                 f"{stats.spec_rounds} verify rounds")
    if eng.expert is not None:
        rows["expert_hit_rate"] = (f"{stats.expert_hit_rate:.0%} "
                                   f"({stats.expert_hits} hits / "
                                   f"{stats.expert_misses} loads, "
                                   f"{stats.expert_evictions} evicted)")
        rows["experts_per_round"] = f"{stats.unique_experts_per_round:.1f}"
        rows["expert_cache_mb"] = f"{stats.expert_cache_bytes/2**20:.1f}"
    print(tele.summary_table(rows, title="serve summary"))
    print(format_peak_breakdown(peak_breakdown_report(stats)))
    print(format_drift(drift_report(g, stats)))
    for rid, req in sorted(sched.done.items()):
        tag = (f" [{req.tenant} p{req.priority}]"
               if serve_trace is not None else "")
        state = ("SHED" if req.rejected else
                 f"admitted r{req.admitted_round} finished "
                 f"r{req.finished_round}")
        print(f"  req{rid}{tag}: arrived r{req.born_round} {state}")
    sched.close()
    export_telemetry(trace_out, metrics_out)
    return outs, stats


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2_base", choices=names(),
                    type=lambda a: a.replace("-", "_").replace(".", "_"),
                    help="architecture id from the config registry "
                    "(dashes/dots tolerated)")
    ap.add_argument("--budget-mb", type=float, default=None)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--num-agents", type=int, default=None)
    ap.add_argument("--pin-window", type=int, default=None)
    ap.add_argument("--max-inflight", type=int, default=4,
                    help="concurrency cap; the planner may pick less "
                    "under a tight budget")
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="Poisson arrivals, requests per round "
                    "(default: all at once)")
    ap.add_argument("--seed", type=int, default=0,
                    help="RNG seed for the prompt/arrival trace; "
                    "recorded in ServeStats.seed for exact replay")
    ap.add_argument("--no-kv-cache", action="store_true",
                    help="paper's per-token re-prefill engine (§V-B2)")
    ap.add_argument("--quant", default="fp32", choices=QUANT_CHOICES,
                    help="shard precision; 'auto' = planner searches "
                    "dtype jointly with the schedule")
    ap.add_argument("--page-size", type=int, default=0,
                    help="paged KV: cache page size in tokens added to "
                    "the planner's search (0 = dense per-request "
                    "reservation)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="paged KV: disable radix-tree prompt-prefix "
                    "page sharing")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="first N prompt tokens identical across "
                    "requests (shared-system-prompt trace)")
    ap.add_argument("--draft-arch", default=None,
                    type=lambda a: a.replace("-", "_").replace(".", "_"),
                    help="speculative serving: architecture id of the "
                    "pinned draft model (needs --page-size; must share "
                    "the target's vocabulary)")
    ap.add_argument("--spec-depth", type=int, default=0,
                    help="draft tokens proposed per verify round; 0 = "
                    "let the planner pick the depth jointly")
    ap.add_argument("--autotune", action="store_true",
                    help="per-device kernel tile/impl autotune for the "
                    "planner's winning (dtype, page size), cached to "
                    "disk (kernels/autotune.py)")
    ap.add_argument("--trace", default=None,
                    help="replay a saved multi-tenant JSON trace "
                    "(repro.data.traces.save_trace) verbatim")
    ap.add_argument("--tenants", type=int, default=0,
                    help="generate a seeded heavy-tailed multi-tenant "
                    "trace with N tenants (Zipf mix, priority classes, "
                    "per-tenant prefix namespaces)")
    ap.add_argument("--chunk-prefill", type=int, default=0,
                    help="split prompts longer than C tokens into "
                    "page-aligned chunks joined into decode rounds "
                    "(needs --page-size; token-identical to monolithic "
                    "prefill)")
    ap.add_argument("--slo-ttft-ms", type=float, default=None,
                    help="time-to-first-token target fed to the "
                    "planner's SLO gate and the scheduler's rounds-based "
                    "accounting")
    ap.add_argument("--slo-tpot-ms", type=float, default=None,
                    help="time-per-output-token target (same SLO "
                    "machinery as --slo-ttft-ms)")
    ap.add_argument("--slo-shed", action="store_true",
                    help="reject requests at admission once their "
                    "best-case TTFT already busts the --slo-ttft-ms "
                    "target")
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="enable the span tracer and write the run as "
                    "Chrome trace-event JSON (open in ui.perfetto.dev: "
                    "one track per loader thread, ledger-bytes counter "
                    "track, scheduler policy instants)")
    ap.add_argument("--metrics-out", default=None, metavar="FILE",
                    help="write the end-of-run metrics-registry "
                    "snapshot (counters/gauges/histograms) as JSON")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    run(args.arch, budget_mb=args.budget_mb, requests=args.requests,
        prompt_len=args.prompt_len, new_tokens=args.new_tokens,
        reduced=not args.full, num_agents=args.num_agents,
        pin_window=args.pin_window, kv_cache=not args.no_kv_cache,
        max_inflight=args.max_inflight, arrival_rate=args.arrival_rate,
        seed=args.seed, quant=args.quant, page_size=args.page_size,
        prefix_cache=not args.no_prefix_cache,
        shared_prefix=args.shared_prefix,
        draft_arch=args.draft_arch, spec_depth=args.spec_depth,
        autotune=args.autotune, trace=args.trace, tenants=args.tenants,
        chunk_prefill=args.chunk_prefill, slo_ttft_ms=args.slo_ttft_ms,
        slo_tpot_ms=args.slo_tpot_ms, slo_shed=args.slo_shed,
        trace_out=args.trace_out, metrics_out=args.metrics_out)


if __name__ == "__main__":
    main()
