"""Reduction of a profiler trace to device busy time, kernel time and
idle gaps.

``extract`` reads the ``.xplane.pb`` the JAX profiler wrote and keeps
three kinds of events, as ``[name, start_ns, duration_ns]`` lists on the
profiler's clock:

* ``device``: per device plane (``/device:TPU:<n>``), the events of its
  ``XLA Ops`` line, one per operation the device ran, named by the HLO
  instruction (``paged_flash_decode.1``, ``copy.31``);
* ``host``: the benchmark's own annotations (names starting ``bench.``).

``reduce`` works on that extract alone, so the arithmetic is tested on
a small recorded extract without a chip.  Busy time is the union of a
device's op intervals inside the window; idle gaps are the holes in that
union, each named after what the host was doing at its midpoint.
"""
from __future__ import annotations

import glob
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
WINDOW = "bench.window"


def op_name(raw: str) -> str:
    """``fusion.3`` from the profiler's ``%fusion.3 = f32[...] fusion(...)``
    (names without an HLO body pass through)."""
    name = raw.split(" = ", 1)[0]
    return name[1:] if name.startswith("%") else name


def find_xplane(trace_dir) -> Optional[Path]:
    found = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    return Path(found[-1]) if found else None


def extract(xplane_path) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(xplane_path))
    device: Dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs += [[op_name(e.name), float(e.start_ns),
                             float(e.duration_ns)] for e in line.events]
            if evs:
                device[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, float(e.start_ns), float(e.duration_ns)]
                         for e in line.events
                         if e.name.startswith(HOST_PREFIX)]
    return {"device": device, "host": host}


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def window_bounds(ex: dict) -> Optional[Tuple[float, float]]:
    """The benchmark's ``bench.window`` annotation, in ns."""
    spans = [(s, s + d) for n, s, d in ex["host"] if n == WINDOW]
    return max(spans, key=lambda x: x[1] - x[0]) if spans else None


def _innermost(spans, t: float) -> Optional[str]:
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else None


def reduce(ex: dict, *, kernels: Sequence[str] = (),
           program_spans: Sequence[Tuple[str, float, float]] = (),
           top: int = 10) -> Optional[dict]:
    """Busy seconds (mean over devices), window seconds, seconds and
    call counts of each named kernel (name prefix, summed over devices),
    the ``top`` device ops by time and the ``top`` longest idle gaps.

    ``program_spans`` are (name, start_ns, end_ns) host spans of the
    program's own, already on the trace clock: a gap inside one is named
    ``<benchmark span>/<program span>``.  Returns None when the trace
    holds no device op or no window."""
    win = window_bounds(ex)
    if win is None or not ex["device"]:
        return None
    lo, hi = win
    bench_spans = [(n, s, s + d) for n, s, d in ex["host"] if n != WINDOW]
    busy, op_time, gaps = [], {}, []
    kern = {k: [0.0, 0] for k in kernels}
    for evs in ex["device"].values():
        ivs = union(clip([(s, s + d) for _, s, d in evs], lo, hi))
        busy.append(sum(e - s for s, e in ivs))
        for name, s, d in evs:
            if s + d <= lo or s >= hi:
                continue
            op_time[name] = op_time.get(name, 0.0) + d
            for k in kernels:
                if name.startswith(k):
                    kern[k][0] += d
                    kern[k][1] += 1
        edges = [lo] + [x for iv in ivs for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                mid = (s + e) / 2
                name = _innermost(bench_spans, mid) or "outside"
                inner = _innermost(program_spans, mid)
                gaps.append((f"{name}/{inner}" if inner else name, e - s))
    ops = sorted(op_time.items(), key=lambda x: -x[1])[:top]
    gaps = sorted(gaps, key=lambda x: -x[1])[:top]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": len(busy),
        "kernels": {k: {"seconds": t / 1e9, "calls": n}
                    for k, (t, n) in kern.items()},
        "device_ops": [[n, t / 1e9] for n, t in ops],
        "idle_gaps": [[n, t / 1e9] for n, t in gaps],
    }
