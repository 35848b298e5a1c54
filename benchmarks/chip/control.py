"""Readings that set a cell's limits, on the chip.

    python3 benchmarks/chip/control.py --workload gpt2_base.stream_decode \
        --seeds 101,102,103 --seconds 8

For each seed, in this one process: a run of the cell as the benchmark
makes it (set-up, a window of ``--seconds``, the reference's check: the
lower readings), then, at the same positions of the same served
sequences, each control in the program's place:

- ``high``: the plain reference one step below the configurations'
  float32 at ``highest`` (three bfloat16 passes), the control;
- ``bfloat16``: the reference in bfloat16, two steps below;
- ``token_altered``: the served tokens with one altered where it was
  produced (the middle token of the longest request), the fault that
  ``logit_gap`` is there to catch.

Each control's numbers go through the cell's own limits and
``check.verdict``, as a run's do, and each prints ``correct``: every
control has to come out false.  Prints one JSON line per seed, then the
largest program reading and the smallest control reading of each number.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run as runmod  # noqa: E402

CONTROLS = ("high", "bfloat16", "token_altered")
NUMBERS = ("logit_gap", "logit_err")


def control_checks(res: dict, reading: dict, limits: dict) -> dict:
    """A control's numbers beside the cell's limits, with the program's
    ledger peak: the checks a run in which the control served would
    have."""
    checks = {"ledger_peak_bytes": res["checks"]["ledger_peak_bytes"]}
    for name in NUMBERS:
        if reading.get(name) is not None:
            checks[name] = {"value": reading[name], "limit": limits[name]}
    return checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    runmod.setup_environment()
    from chipbench import check
    from chipbench.runner import run
    from chipbench.spec import load_cell

    cell = load_cell(args.workload, runmod.ROOT)
    limits = cell.cell["limits"]
    program, control = [], []
    for seed in [int(s) for s in args.seeds.split(",")]:
        res = run(cell, seed, args.seconds, False,
                  t_start=time.perf_counter(), control=True,
                  log=lambda s: print(s, file=sys.stderr, flush=True))
        verdicts = {}
        for c in CONTROLS:
            checks = control_checks(res, res["control"][c], limits)
            verdicts[c] = {"correct": check.verdict(checks),
                           "checks": checks}
        row = {"seed": seed, "correct": res["correct"],
               "program": {k: c["value"] for k, c in res["checks"].items()},
               "control": verdicts, "readings": res["control"],
               "metrics": res["metrics"]}
        program.append(row["program"])
        control.append(res["control"])
        print(json.dumps(row), flush=True)
    summary = {"workload": args.workload, "limits": limits}
    for name in NUMBERS:
        summary[f"program_{name}_max"] = max(p[name] for p in program)
        for c in CONTROLS:
            vals = [x[c][name] for x in control if x[c][name] is not None]
            summary[f"{c}_{name}_min"] = min(vals) if vals else None
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
