"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload gpt2_base.stream_decode \
        --seed 7 --seconds 45 --trace 0

Run from the root of a checkout.  The program under test is the
``repro`` package under ``src/``; everything else (traffic, weights, the
plain reference, trace reduction, peaks, metric readers) lives in this
directory and is found by the names ``BENCHMARK.json`` gives.

Prints progress lines, then as its last stdout line one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` a ``breakdown``), and ``checks``: each compared number
beside its limit, which also end stderr.  ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from a
profiled window.

Exits non-zero and prints no result when JAX finds no accelerator or
fewer chips than the cell asks for, or when the checkout lacks ``src/``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = HERE / ".jax_cache"


# glibc's mallopt parameters
M_TRIM_THRESHOLD, M_MMAP_THRESHOLD, M_ARENA_MAX = -1, -3, -8


def keep_freed_memory() -> None:
    """Tell glibc's malloc to keep the memory it frees: one arena, no
    trim, no mmap under 1 GiB.  The loading agents parse each shard into
    fresh host arrays and free them once on the device.  Under glibc's
    defaults that memory can go back to the kernel and be mapped afresh
    for the next shard, and on a TPU v5e host a run's rounds sat at one
    of two speeds, set per process (the slower one 25% slower; yi's
    180 MB arrays took twice as long to load).  With this policy every
    run reuses the same host memory, as a serving process tuned for a
    fixed set of shard sizes would.  Call it before any thread starts."""
    import ctypes

    libc = ctypes.CDLL("libc.so.6")
    for param, value in ((M_ARENA_MAX, 1),
                         (M_TRIM_THRESHOLD, 2 ** 31 - 1),
                         (M_MMAP_THRESHOLD, 2 ** 30)):
        if libc.mallopt(param, value) != 1:
            raise OSError(f"mallopt({param}, {value}) failed")


def setup_environment() -> bool:
    """Put the program and the harness on the import path, the
    persistent compile cache at its fixed place in the checkout and the
    allocator in its steady policy, before JAX is imported.  False when
    the checkout holds no program."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        return False
    keep_freed_memory()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    sys.path[:0] = [str(src), str(HERE)]
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    if not setup_environment():
        print(f"chipbench: no program under {ROOT / 'src'}: run from a "
              "checkout", file=sys.stderr)
        return 2
    from chipbench import check
    from chipbench.runner import NoChip, run
    from chipbench.spec import load_cell

    cell = load_cell(args.workload, ROOT)
    try:
        result = run(cell, args.seed, args.seconds, bool(args.trace),
                     t_start=T_START,
                     log=lambda s: print(s, flush=True))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    sys.stdout.flush()
    for line in check.lines(result["checks"]):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
