"""Benchmark harness: one bench per paper table/figure + roofline/kernels.

    PYTHONPATH=src python -m benchmarks.run [--only fig2,table2,...]

Prints ``name,us_per_call,derived`` CSV; detailed rows land in
experiments/bench/*.json, and each entry's headline CSV lines are also
written to a repo-root ``BENCH_<entry>.json`` so the perf trajectory
stays machine-readable across PRs without parsing stdout.  Every run
additionally APPENDS one JSONL line per entry (with the git sha and
date) to ``BENCH_history.jsonl`` — ``benchmarks/trajectory.py`` diffs
the two most recent runs of each entry and flags >10% regressions.
"""
from __future__ import annotations

import argparse
import datetime
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
HISTORY = ROOT / "BENCH_history.jsonl"

BENCHES = {
    "fig2": "benchmarks.bench_memory_distribution",
    "fig3": "benchmarks.bench_load_vs_infer",
    "table2": "benchmarks.bench_table2_latency",
    "table3": "benchmarks.bench_table3_memory",
    "fig7": "benchmarks.bench_fig7_constraints",
    "decode": "benchmarks.bench_decode",
    "batch_decode": "benchmarks.bench_batch_decode",
    "prefix": "benchmarks.bench_prefix",
    "serve_slo": "benchmarks.bench_serve_slo",
    "spec": "benchmarks.bench_spec_decode",
    "quant": "benchmarks.bench_quant",
    "moe": "benchmarks.bench_moe_stream",
    "roofline": "benchmarks.bench_roofline",
    "kernels": "benchmarks.bench_kernels",
}


def _headline_rows(lines):
    """Parse ``name,us_per_call,derived`` CSV lines into dicts."""
    rows = []
    for line in lines:
        name, us, derived = line.split(",", 2)
        rows.append({"name": name, "us_per_call": float(us),
                     "derived": derived})
    return rows


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=ROOT,
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:  # noqa: BLE001 — no git / not a checkout
        return "unknown"


def append_history(entry: str, rows, seconds: float,
                   path: Path = HISTORY) -> None:
    """One JSONL line per bench run: the machine-readable perf
    trajectory ``benchmarks/trajectory.py`` regresses against."""
    rec = {"entry": entry, "sha": _git_sha(),
           "date": datetime.datetime.now(datetime.timezone.utc)
                   .strftime("%Y-%m-%dT%H:%M:%SZ"),
           "seconds": round(seconds, 2), "rows": rows}
    with path.open("a") as f:
        f.write(json.dumps(rec) + "\n")


def write_summary(entry: str, lines, seconds: float) -> Path:
    out = ROOT / f"BENCH_{entry}.json"
    rows = _headline_rows(lines)
    out.write_text(json.dumps(
        {"entry": entry, "seconds": round(seconds, 2),
         "rows": rows}, indent=1) + "\n")
    append_history(entry, rows, seconds)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(BENCHES))
    args = ap.parse_args()
    names = list(BENCHES) if not args.only else args.only.split(",")

    import importlib

    from repro import compile_cache
    compile_cache.enable()
    failures = 0
    print("name,us_per_call,derived")
    for name in names:
        mod = importlib.import_module(BENCHES[name])
        t0 = time.time()
        lines = []
        try:
            for line in mod.run():
                lines.append(line)
                print(line, flush=True)
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            failures += 1
        else:
            write_summary(name, lines, time.time() - t0)
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
    if failures:
        raise SystemExit(f"{failures} bench(es) failed")


if __name__ == "__main__":
    main()
