"""A traced run of one cell, with an account of its rounds from every
thread's telemetry spans and from the program's ``hermes.*`` events in
the profiler trace.

    python3 benchmarks/chip/round_spans.py --workload gpt2_base.stream_decode \
        --seed 7 --seconds 45 [--extract out.json]

The run is ``run.py --trace 1``'s (``runner.run``, unchanged); this
script keeps two things that run drops: the loading agents' spans (the
run keeps the main thread's) and the program's spans in the profiler
trace.  Before the run's own result line it prints:

- ``bench.round_spans``: for the window's slowest and median round, the
  self seconds under each inference-agent span, the ``serve_round``'s own
  seconds and the loading agents' span seconds of the same ``stream``;
- ``bench.agent_spans``: ``shard_read_gbps`` and ``budget_wait_share``
  over the window;
- ``bench.hermes_gaps``: the ten longest idle gaps of the device, named
  from the ``hermes.*`` events on the trace's clock
  (``bench.step/shard_wait@shard_read``).

``--extract`` writes the device ops, ``bench.*`` and ``hermes.*`` host
events of the window's first ``--extract-seconds`` as a JSON extract,
the window cut to that span, for the CPU tests.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run as runmod  # noqa: E402


def small_extract(ex: dict, events, seconds: float) -> dict:
    """The part of an extract, and of its ``hermes.*`` events, that
    lies in the window's first ``seconds``, the window cut to match."""
    from chipbench import trace as tracemod

    lo, _ = tracemod.window_bounds(ex)
    hi = lo + seconds * 1e9

    def within(s, d):
        return s >= lo and s + d <= hi

    host = [h for h in ex["host"]
            if h[0] != tracemod.WINDOW and within(h[1], h[2])]
    return {"device": {p: [e for e in evs if within(e[1], e[2])]
                       for p, evs in ex["device"].items()},
            "host": [[tracemod.WINDOW, lo, hi - lo]] + host,
            "hermes": [e for e in events if within(e[2], e[3])]}


def traced_run(cell, seed: int, seconds: float, **kw):
    """``runner.run(cell, seed, seconds, True, **kw)``, keeping the
    tracer it enables and the trace it reads.  Returns the run's result,
    every thread's spans, and the trace extract and its ``hermes.*``
    events (None where the run took no trace)."""
    from chipbench import runner, spans
    from chipbench import trace as tracemod
    from repro.core import telemetry as tele

    kept = {}
    enable, extract = tele.enable, tracemod.extract

    def keep_tracer(tracer=None):
        kept["tracer"] = enable(tracer)
        return kept["tracer"]

    def keep_events(xplane):
        kept["ex"] = extract(xplane)
        kept["events"] = spans.hermes_events(xplane)
        return kept["ex"]

    tele.enable, tracemod.extract = keep_tracer, keep_events
    try:
        result = runner.run(cell, seed, seconds, True, **kw)
    finally:
        tele.enable, tracemod.extract = enable, extract
    return (result, list(kept["tracer"].spans), kept.get("ex"),
            kept.get("events"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--extract", default=None)
    ap.add_argument("--extract-seconds", type=float, default=1.5)
    args = ap.parse_args(argv)
    if not runmod.setup_environment():
        print("round_spans: no program under src/", file=sys.stderr)
        return 2
    from chipbench import spans
    from chipbench.runner import NoChip
    from chipbench.spec import load_cell

    cell = load_cell(args.workload, runmod.ROOT)
    try:
        result, every, ex, events = traced_run(
            cell, args.seed, args.seconds, t_start=T_START,
            log=lambda s: print(s, flush=True))
    except NoChip as e:
        print(f"round_spans: {e}", file=sys.stderr)
        return 3
    print("bench.round_spans: " + json.dumps(spans.round_account(every)),
          flush=True)
    print("bench.agent_spans: " + json.dumps({
        "shard_read_gbps": spans.shard_read_gbps(every),
        "budget_wait_share": spans.budget_wait_share(every)}), flush=True)
    if ex is not None:
        print("bench.hermes_gaps: "
              + json.dumps(spans.name_gaps(ex, events)), flush=True)
        if args.extract:
            with open(args.extract, "w") as f:
                json.dump(small_extract(ex, events, args.extract_seconds),
                          f)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
