"""Expert-streaming PIPELOAD vs whole-layer MoE streaming (beyond-paper).

One 128-expert top-8 MoE stack (the qwen3-moe routing shape at smoke
dims), one shared memory budget, two checkpoint layouts of the SAME
weights:

  * ``whole`` — the paper's layer shards: every decode round re-streams
    each layer's full FFN, all 128 experts, even though top-8 routing
    touches ~6% of them.
  * ``split`` — expert-split shards (attention+router per layer + one
    shard per expert): attention+router stream eagerly, the round's
    activated expert union is demand-loaded after the router runs, and
    the LRU ExpertCache (sized from the same budget's headroom) turns
    repeat activations into disk-free hits.

Both engines run the identical KV-cache generation workload with
``pin_window=0`` so every round pays its layer stream — the measured
decode-phase bytes-per-round ratio is pure routing sparsity.  Outputs
are token-identical (the streamed combine is the oracle's math over the
activated experts), reported as ``tok_agree``.
"""
from __future__ import annotations

import functools

import numpy as np

from repro.configs import get_config
from repro.core import PipeloadEngine
from repro.checkpoint import ensure_partitioned, load_manifest
from repro.models.api import build_model
from benchmarks.common import CKPT_ROOT, csv_line, emit

import jax

PROMPT_LEN = 32
NEW_TOKENS = 8
AGENTS = 4
LAYERS = 4
N_EXPERTS, TOP_K = 128, 8


def _config():
    cfg = get_config("qwen3_moe_30b_a3b").reduced()
    return cfg.with_(name="qwen3-moe-smoke-128e", num_layers=LAYERS,
                     n_experts=N_EXPERTS, top_k=TOP_K, expert_d_ff=32)


def ensure_ckpts(cfg):
    paths = {"whole": CKPT_ROOT / "moe_stream_whole",
             "split": CKPT_ROOT / "moe_stream_split"}
    params = functools.cache(
        lambda: build_model(cfg).init(jax.random.PRNGKey(0)))
    ensure_partitioned(paths["whole"], cfg, params, expert_split=False)
    ensure_partitioned(paths["split"], cfg, params, expert_split=True)
    return paths


def _decode_bytes(stats, shards) -> int:
    """Disk bytes read during the decode phase (after the first sampled
    token; the prefill round's loads are excluded)."""
    token_ts = [e[0] for e in stats.events if e[1] == "token"]
    if not token_ts:
        return 0
    t_dec = min(token_ts)
    return sum(shards[e[2]]["bytes"] for e in stats.events
               if e[1] == "load_end" and e[0] >= t_dec)


def run():
    cfg = _config()
    paths = ensure_ckpts(cfg)
    manifests = {k: load_manifest(p) for k, p in paths.items()}
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (1, PROMPT_LEN))
    total = PROMPT_LEN + NEW_TOKENS

    # one shared budget, sized off the WHOLE-layer manifest so the dense
    # baseline can stream: other + KV pages + 2.5 full layers of headroom
    man_w = manifests["whole"]
    other = sum(s["bytes"] for s in man_w["shards"]
                if s["kind"] != "layer")
    lb = max(s["bytes"] for s in man_w["shards"] if s["kind"] == "layer")
    kv = cfg.num_layers * cfg.cache_bytes(1, total)
    budget = other + kv + int(2.5 * lb)

    rows, outs = [], {}
    dec_rounds = NEW_TOKENS - 1
    for layout, path in paths.items():
        eng = PipeloadEngine(path, cfg, mode="pipeload", num_agents=AGENTS,
                             pin_window=0, budget_bytes=budget)
        eng.warmup(1, PROMPT_LEN, decode=True, total_len=total)
        out, stats = eng.run_generate(toks, NEW_TOKENS, kv_cache=True)
        outs[layout] = np.asarray(out)[:, PROMPT_LEN:]
        rows.append({
            "model": cfg.name, "layout": layout,
            "n_experts": cfg.n_experts, "top_k": cfg.top_k,
            "num_layers": cfg.num_layers,
            "prompt_len": PROMPT_LEN, "new_tokens": NEW_TOKENS,
            "budget_bytes": budget, "num_agents": AGENTS,
            "latency_s": stats.latency_s, "per_token_s": stats.per_token_s,
            "peak_bytes": stats.peak_bytes,
            "within_budget": stats.peak_bytes <= budget,
            "streamed_bytes": stats.streamed_bytes,
            "decode_streamed_bytes": _decode_bytes(stats, eng.shards),
            "decode_bytes_per_round":
                _decode_bytes(stats, eng.shards) / dec_rounds,
            "loads": stats.loads,
            "expert_hits": stats.expert_hits,
            "expert_misses": stats.expert_misses,
            "expert_evictions": stats.expert_evictions,
            "expert_hit_rate": stats.expert_hit_rate,
            "expert_cache_bytes": stats.expert_cache_bytes,
            "unique_experts_per_round": stats.unique_experts_per_round,
        })
        del eng

    agree = float((outs["split"] == outs["whole"]).mean())
    for r in rows:
        r["token_agreement"] = agree
    emit(rows, "moe")

    whole = next(r for r in rows if r["layout"] == "whole")
    split = next(r for r in rows if r["layout"] == "split")
    reduction = (whole["decode_bytes_per_round"]
                 / max(split["decode_bytes_per_round"], 1))
    lines = [
        csv_line("moe[whole]", whole["per_token_s"] * 1e6,
                 f"decode_MB_per_round="
                 f"{whole['decode_bytes_per_round']/2**20:.2f},"
                 f"within_budget={whole['within_budget']}"),
        csv_line("moe[split]", split["per_token_s"] * 1e6,
                 f"decode_bytes_per_round_reduction_x={reduction:.2f},"
                 f"decode_MB_per_round="
                 f"{split['decode_bytes_per_round']/2**20:.2f},"
                 f"expert_hit_rate={split['expert_hit_rate']:.2f},"
                 f"unique_experts_per_round="
                 f"{split['unique_experts_per_round']:.1f},"
                 f"tok_agree={agree:.2f},"
                 f"within_budget={split['within_budget']}"),
    ]
    return lines
