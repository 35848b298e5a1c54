"""Speculative decoding vs plain paged decode, one budget (PR 6).

Single-request decode is PIPELOAD's worst regime: every generated token
pays a full weight stream (all non-pinned layers through the Loading
Agents) to compute ONE token.  Speculative decoding amortises exactly
that: a draft proposes ``DEPTH`` tokens, the target scores the whole
window ``[last committed, d_1..d_k]`` in ONE stacked verify round over
the paged KV block tables (kernels/paged_decode.py), and the accepted
prefix plus the target's bonus token commit together — up to
``DEPTH + 1`` tokens per weight stream.

Both arms run the SAME engine, checkpoint, page size and memory budget:

  * ``plain`` — non-speculative paged KV decode (PR 5 path): one token
    per pipeline round.
  * ``spec``  — ``run_generate(speculative=...)`` with the draft set to
    the TARGET ITSELF (self-speculation).  Acceptance is then exactly
    1.0 — the documented DEGENERATE CEILING: it isolates the round
    amortisation (what the verify machinery buys at a given acceptance
    rate) from draft quality, which is a model-selection question, not
    an engine one.  Real drafts land between this ceiling and the
    plain arm; the planner's acceptance-rate model interpolates.

The acceptance check is ``speedup >= 2.0`` (single-request decode
tokens/s) with BOTH arms inside the same budget and
``tok_agree == 1.0`` — speculative greedy output is bitwise identical
to plain paged decode (rejected suffixes roll back by refcount, never
by copy).  Results land in ``experiments/bench/spec.json``; run.py
writes the headline to repo-root ``BENCH_spec.json``.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.checkpoint import ensure_partitioned, load_manifest
from repro.configs import get_config
from repro.core import PipeloadEngine
from repro.core.engine import SpecConfig
from repro.models.api import build_model
from benchmarks.common import CKPT_ROOT, csv_line, emit

PROMPT_LEN = 32
NEW_TOKENS = 64
PAGE = 16
DEPTHS = (2, 4)             # headline = deepest window
AGENTS = 4


def _cfg():
    return get_config("gpt2_base").with_(
        name="gpt2-specbench", num_layers=8, d_model=256, n_heads=8,
        n_kv_heads=8, head_dim=32, d_ff=1024, vocab_size=2000,
        vocab_pad_to=8, dtype="float32", remat=False)


def _ckpt(cfg):
    return ensure_partitioned(
        CKPT_ROOT / "gpt2_specbench", cfg,
        lambda: build_model(cfg).init(jax.random.PRNGKey(0)))


def _gen(ckpt, cfg, prompt, budget, spec):
    eng = PipeloadEngine(ckpt, cfg, mode="pipeload", num_agents=AGENTS,
                         budget_bytes=budget, page_size=PAGE)
    # untimed short run compiles every executable the timed run needs
    # (prefill, decode/verify, draft chain) so the clock sees rounds,
    # not jit
    eng.run_generate(prompt, 2, kv_cache=True, speculative=spec)
    t0 = time.perf_counter()
    out, st = eng.run_generate(prompt, NEW_TOKENS, kv_cache=True,
                               speculative=spec)
    dt = time.perf_counter() - t0
    del eng
    return np.asarray(out), st, dt


def run():
    cfg = _cfg()
    ckpt = _ckpt(cfg)
    man = load_manifest(ckpt)
    # one budget for every arm, sized for the SPEC floor: the self-draft
    # pins the whole model next to the streamed layers, its dense cache
    # row, and the paged pool + verify-window overhang
    total = PROMPT_LEN + NEW_TOKENS
    cache = cfg.num_layers * cfg.cache_bytes(1, total + max(DEPTHS) + 1)
    budget = 2 * man["total_bytes"] + 3 * cache

    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (1, PROMPT_LEN))

    base_out, base_st, base_s = _gen(ckpt, cfg, prompt, budget, None)
    rows, lines = [], []
    for depth in DEPTHS:
        spec = SpecConfig(ckpt, cfg, depth=depth)   # self-speculation
        out, st, dt = _gen(ckpt, cfg, prompt, budget, spec)
        agree = float(np.array_equal(out, base_out))
        speedup = base_s / dt
        within = (st.peak_bytes <= budget
                  and base_st.peak_bytes <= budget)
        rows.append({
            "model": cfg.name, "prompt_len": PROMPT_LEN,
            "new_tokens": NEW_TOKENS, "page_size": PAGE,
            "spec_depth": depth, "budget_bytes": budget,
            "plain_latency_s": base_s,
            "plain_tokens_per_s": NEW_TOKENS / base_s,
            "plain_peak_bytes": base_st.peak_bytes,
            "plain_loads": base_st.loads,
            "spec_latency_s": dt,
            "spec_tokens_per_s": NEW_TOKENS / dt,
            "spec_peak_bytes": st.peak_bytes,
            "spec_loads": st.loads,
            "spec_rounds": st.spec_rounds,
            "acceptance_rate": st.acceptance_rate,
            "speedup": speedup,
            "within_budget": within,
            "tok_agree": agree,
        })
        lines.append(csv_line(
            f"spec[depth={depth} page={PAGE}]",
            dt / NEW_TOKENS * 1e6,
            f"speedup_vs_plain={speedup:.2f},"
            f"tok_s={NEW_TOKENS / dt:.1f},"
            f"plain_tok_s={NEW_TOKENS / base_s:.1f},"
            f"rounds={st.spec_rounds}_vs_{NEW_TOKENS},"
            f"acceptance={st.acceptance_rate:.2f},"
            f"within_budget={within},"
            f"tok_agree={agree:.2f}"))
    emit(rows, "spec")
    return lines
