"""Serve a GPT-2-class model with PIPELOAD under a memory budget.

    PYTHONPATH=src python examples/serve_pipeload.py --budget-mb 400

Shows the full Hermes flow: partition -> profile -> plan -> execute, and
compares baseline / pipeswitch / pipeload / pipeload+kv latency+memory on
this machine (pipeload+kv is the beyond-paper KV-cache decode path; its
(num_agents, pin_window) come from the generation-aware planner).

``--poisson RATE`` adds the continuous-batching finale: RATE requests
per round arrive as a Poisson process and the scheduler amortises each
weight-stream round across everyone in flight — watch the per-request
admitted/finished rounds interleave while peak memory stays put.

``--quant int8|int4`` closes with quantized weight streaming: the same
KV-cache run over per-channel integer shards — same schedule, ~4x/8x
fewer bytes streamed and resident (greedy tokens usually match at int8).
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import numpy as np

from repro.checkpoint import ensure_partitioned
from repro.configs import get_config
from repro.core import BatchScheduler, Hermes, PipeloadEngine
from repro.models.api import build_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget-mb", type=float, default=400.0)
    ap.add_argument("--new-tokens", type=int, default=6)
    ap.add_argument("--poisson", type=float, default=0.5,
                    help="continuous-batching demo arrival rate "
                    "(requests/round; 0 disables the demo)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--quant", default="int8",
                    choices=["none", "int8", "int4"],
                    help="quantized-streaming finale dtype "
                    "('none' disables it)")
    args = ap.parse_args()

    cfg = get_config("gpt2_base")
    ckpt = Path("/tmp/repro_example_gpt2")
    ensure_partitioned(ckpt, cfg,
                       lambda: build_model(cfg).init(jax.random.PRNGKey(0)))

    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 4))
    budget = int(args.budget_mb * 2**20)

    h = Hermes(ckpt, cfg)
    prof = h.profile(batch=1, seq=4)
    print(f"profile: t_load={prof['layer_t_load']*1e3:.1f}ms "
          f"t_comp={prof['layer_t_comp']*1e3:.1f}ms "
          f"layer={prof['layer_bytes']/2**20:.1f}MB")
    entry = h.plan([budget])[0]
    print(f"planner @ {args.budget_mb:.0f}MB -> {entry.num_agents} agents "
          f"(predicted {entry.predicted_latency_s*1e3:.0f}ms/pass)")

    for mode, agents, bud in [("baseline", 1, None), ("pipeswitch", 1, None),
                              ("pipeload", entry.num_agents, budget)]:
        eng = PipeloadEngine(ckpt, cfg, mode=mode, num_agents=agents,
                             budget_bytes=bud).warmup(1, 4)
        out, st = eng.run_generate(toks, args.new_tokens)
        print(f"{mode:11s} m={agents}: {st.latency_s:6.2f}s  "
              f"peak={st.peak_bytes/2**20:7.1f}MB  loads={st.loads}")

    g = h.plan_generate([budget], batch=1, prompt_len=toks.shape[1],
                        new_tokens=args.new_tokens)[0]
    eng = PipeloadEngine(ckpt, cfg, mode="pipeload",
                         num_agents=g.num_agents, pin_window=g.pin_window,
                         budget_bytes=budget if g.feasible else None)
    eng.warmup(1, 4, decode=True, total_len=toks.shape[1] + args.new_tokens)
    out, st = eng.run_generate(toks, args.new_tokens, kv_cache=True)
    print(f"pipeload+kv m={g.num_agents} pin={g.pin_window}: "
          f"{st.latency_s:6.2f}s  peak={st.peak_bytes/2**20:7.1f}MB  "
          f"loads={st.loads}  cache={st.cache_bytes/2**20:.1f}MB")

    if args.quant != "none":
        # ---- quantized weight streaming: same schedule, integer shards
        hq = h.quantized(args.quant)
        qeng = PipeloadEngine(hq.dir, cfg, mode="pipeload",
                              num_agents=g.num_agents,
                              pin_window=g.pin_window,
                              budget_bytes=budget if g.feasible else None)
        qeng.warmup(1, 4, decode=True,
                    total_len=toks.shape[1] + args.new_tokens)
        qout, qst = qeng.run_generate(toks, args.new_tokens, kv_cache=True)
        match = bool(np.array_equal(np.asarray(qout), np.asarray(out)))
        print(f"pipeload+kv[{args.quant}]: {qst.latency_s:6.2f}s  "
              f"peak={qst.peak_bytes/2**20:7.1f}MB  "
              f"streamed={qst.streamed_bytes/2**20:.0f}MB "
              f"(vs {st.streamed_bytes/2**20:.0f}MB fp32)  "
              f"tokens_match={match}")

    if args.poisson:
        # ---- continuous batching: Poisson arrivals share weight streams
        n = args.requests
        gs = h.plan_generate([budget], prompt_len=toks.shape[1],
                             new_tokens=args.new_tokens,
                             max_inflight=n)[0]
        fits = gs.feasible
        if not fits:          # demo fallback, like the pipeload+kv run
            gs = h.plan_generate([None], prompt_len=toks.shape[1],
                                 new_tokens=args.new_tokens,
                                 max_inflight=n)[0]
        eng = PipeloadEngine(ckpt, cfg, mode="pipeload",
                             num_agents=gs.num_agents,
                             pin_window=gs.pin_window,
                             budget_bytes=budget if fits else None)
        sched = BatchScheduler(
            eng, max_inflight=gs.inflight,
            max_total_len=toks.shape[1] + args.new_tokens)
        sched.warmup(prompt_lens=[toks.shape[1]])
        rng = np.random.default_rng(0)
        arrivals = np.floor(np.cumsum(
            rng.exponential(1.0 / args.poisson, size=n))).astype(int)
        for i in range(n):
            p = rng.integers(0, cfg.vocab_size, (toks.shape[1],))
            sched.submit(p, args.new_tokens, arrival_round=int(arrivals[i]))
        outs, ss = sched.run()
        print(f"scheduler   m={gs.num_agents} pin={gs.pin_window} "
              f"inflight<={gs.inflight}: {ss.latency_s:6.2f}s  "
              f"peak={ss.peak_bytes/2**20:7.1f}MB  loads={ss.loads}  "
              f"{ss.tokens_per_s:.1f} tok/s over {ss.rounds} rounds")
        for rid, req in sorted(sched.done.items()):
            print(f"  req{rid}: arrived r{req.arrival_round} admitted "
                  f"r{req.admitted_round} finished r{req.finished_round}")


if __name__ == "__main__":
    main()
