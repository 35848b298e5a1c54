"""Paged KV + radix prefix sharing vs dense reservations, one budget.

The workload is a fleet of requests behind one long SHARED system
prompt (the multi-user serving shape the paged subsystem exists for):
every prompt opens with the same ``SHARED_LEN`` tokens and ends with a
per-request tail.  Both arms serve the SAME requests through the
continuous-batching scheduler under the SAME memory budget:

  * ``dense`` — today's reservation path: every request charges
    ``num_layers x cache_bytes(max_total_len)`` to the ledger for its
    whole lifetime, so the budget admits only a few requests at a time
    and the rest wait in waves.
  * ``paged`` — core/kv_pages.py: admission charges pages actually
    mapped, the radix tree maps the shared prefix's pages ONCE across
    the fleet, and decode grows one page at a time — so the same budget
    admits the whole fleet at once and each PIPELOAD round's weight
    stream serves every request.

The acceptance check is ``speedup >= 1.5`` (aggregate tokens/s) with a
LOWER KV ledger peak on the paged arm and ``tok_agree == 1.0``
(page-gathered decode is bit-identical to the dense padded cache, so
greedy outputs match token for token).  Results land in
``experiments/bench/prefix.json``; run.py writes the headline summary
to repo-root ``BENCH_prefix.json``.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.checkpoint import ensure_partitioned, load_manifest
from repro.configs import get_config
from repro.core import BatchScheduler, PipeloadEngine
from repro.models.api import build_model
from benchmarks.common import CKPT_ROOT, csv_line, emit

# KV-bound serving shape: small layers, long shared prompt — the regime
# where cache bytes (not weights) gate admission.
SHARED_LEN = 448            # shared system prompt (7 full pages)
UNIQ_LEN = 64               # per-request tail -> prompt_len = 512
NEW_TOKENS = 16
PAGE = 64                   # 512 + 16 -> 9 pages; MAX_TOTAL pads to 576
MAX_TOTAL = 576             # both arms pad caches here (bitwise parity)
REQUESTS = 8
AGENTS = 4


def _cfg():
    return get_config("gpt2_base").with_(
        name="gpt2-kvbench", num_layers=8, d_model=256, n_heads=8,
        n_kv_heads=8, head_dim=32, d_ff=1024, vocab_size=2000,
        vocab_pad_to=8, dtype="float32", remat=False)


def _ckpt(cfg):
    return ensure_partitioned(
        CKPT_ROOT / "gpt2_kvbench", cfg,
        lambda: build_model(cfg).init(jax.random.PRNGKey(0)))


def _serve(ckpt, cfg, prompts, budget, page_size):
    eng = PipeloadEngine(ckpt, cfg, mode="pipeload", num_agents=AGENTS,
                         budget_bytes=budget, page_size=page_size or None)
    sched = BatchScheduler(eng, max_inflight=REQUESTS,
                           max_total_len=MAX_TOTAL,
                           page_size=page_size or None)
    sched.warmup(prompt_lens=[SHARED_LEN + UNIQ_LEN])
    rids = [sched.submit(p, NEW_TOKENS) for p in prompts]
    t0 = time.perf_counter()
    outs, st = sched.run()
    dt = time.perf_counter() - t0
    del eng, sched
    return rids, outs, st, dt


def run():
    cfg = _cfg()
    ckpt = _ckpt(cfg)
    man = load_manifest(ckpt)
    layer_b = man["layer_bytes"] // cfg.num_layers
    other = man["total_bytes"] - man["layer_bytes"]
    per_req_dense = cfg.num_layers * cfg.cache_bytes(1, MAX_TOTAL)
    # ONE budget for both arms, sized so the dense reservation admits
    # ~3 concurrent requests (3.5 caches + other + one streaming layer)
    budget = other + layer_b + int(3.5 * per_req_dense)

    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab_size, (SHARED_LEN,))
    prompts = [np.concatenate([shared,
                               rng.integers(0, cfg.vocab_size, (UNIQ_LEN,))])
               for _ in range(REQUESTS)]

    d_rids, d_outs, d_st, d_s = _serve(ckpt, cfg, prompts, budget, 0)
    p_rids, p_outs, p_st, p_s = _serve(ckpt, cfg, prompts, budget, PAGE)

    tokens = REQUESTS * NEW_TOKENS
    agree = np.mean([float(np.array_equal(p_outs[pr], d_outs[dr]))
                     for pr, dr in zip(p_rids, d_rids)])
    speedup = (tokens / p_s) / (tokens / d_s)
    row = {
        "model": cfg.name, "requests": REQUESTS,
        "shared_prefix": SHARED_LEN, "prompt_len": SHARED_LEN + UNIQ_LEN,
        "new_tokens": NEW_TOKENS, "page_size": PAGE,
        "max_total_len": MAX_TOTAL, "budget_bytes": budget,
        "dense_latency_s": d_s, "dense_tokens_per_s": tokens / d_s,
        "dense_peak_bytes": d_st.peak_bytes,
        "dense_kv_peak_bytes": d_st.cache_bytes_peak,
        "dense_max_inflight": d_st.max_inflight_seen,
        "dense_rounds": d_st.rounds, "dense_loads": d_st.loads,
        "paged_latency_s": p_s, "paged_tokens_per_s": tokens / p_s,
        "paged_peak_bytes": p_st.peak_bytes,
        "paged_kv_peak_bytes": p_st.cache_bytes_peak,
        "paged_max_inflight": p_st.max_inflight_seen,
        "paged_rounds": p_st.rounds, "paged_loads": p_st.loads,
        "prefix_hit_pages": p_st.prefix_hit_pages,
        "pages_allocated": p_st.pages_allocated,
        "pool_pages_peak": p_st.pool_pages_peak,
        "cow_copies": p_st.cow_copies,
        "preemptions": p_st.preemptions,
        "speedup": speedup,
        "kv_peak_ratio": d_st.cache_bytes_peak / p_st.cache_bytes_peak,
        "within_budget": (p_st.peak_bytes <= budget
                          and d_st.peak_bytes <= budget),
        "tok_agree": float(agree),
    }
    emit([row], "prefix")
    return [csv_line(
        f"prefix[shared={SHARED_LEN} page={PAGE}]",
        p_s / tokens * 1e6,
        f"speedup_vs_dense={speedup:.2f},"
        f"tok_s={tokens / p_s:.1f},"
        f"inflight={p_st.max_inflight_seen}_vs_{d_st.max_inflight_seen},"
        f"kv_peak_mb={p_st.cache_bytes_peak / 2**20:.1f}"
        f"_vs_{d_st.cache_bytes_peak / 2**20:.1f},"
        f"prefix_hit_pages={p_st.prefix_hit_pages},"
        f"within_budget={row['within_budget']},"
        f"tok_agree={agree:.2f}")]
