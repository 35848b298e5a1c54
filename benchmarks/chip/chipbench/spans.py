"""The program's telemetry spans, as the per-layer metrics and the
round-span account read them.

A span is ``(name, thread, start_s, end_s, args)``, as
``repro.core.telemetry`` records it on ``time.perf_counter``.
``Run.spans`` holds the inference agent's (main-thread) spans of the
window; the account of a round (``round_account``) and the loading
agents' rates (``shard_read_gbps``, ``budget_wait_share``) need every
thread's.  One round's spans share the ``stream`` argument, the number of
the round's prefetch stream, on every thread.

The program also writes each enabled span into the profiler trace as a
``hermes.<name>`` host event with its arguments as stats.
``hermes_events`` reads them from the ``.xplane.pb``, on the trace's own
clock, and ``name_gaps`` names the device's idle gaps from them: the
benchmark's span, the innermost inference-agent span and the loading
agent's span at the gap's midpoint, as ``bench.step/shard_wait@shard_read``.
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Sequence

from chipbench import trace as tracemod

PREFIX = "hermes."
ROUND = "stream_round"
SERVE = "serve_round"
# the inference agent's spans inside a round, outermost first
INFERENCE = ("stream_round", "shard_wait", "compute", "layer_dispatch",
             "layer_sync")
AGENT = ("shard_acquire", "shard_load", "shard_read", "shard_put",
         "shard_destroy")


def _dur(s) -> float:
    return s[3] - s[2]


def _inside(inner, outer) -> bool:
    return (inner[1] == outer[1] and outer[2] <= inner[2]
            and inner[3] <= outer[3])


def _named(spans, name: str) -> list:
    return [s for s in spans if s[0] == name]


def share_of_rounds(spans, name: str) -> Optional[float]:
    """Seconds of ``name`` spans inside the window's ``stream_round``
    spans, as a share (%) of those rounds' seconds; None without
    rounds or without a single ``name`` span (a program that has no such
    span)."""
    if not spans:
        return None
    rounds = _named(spans, ROUND)
    total = sum(_dur(r) for r in rounds)
    parts = [s for s in _named(spans, name)
             if any(_inside(s, r) for r in rounds)]
    if total <= 0 or not parts:
        return None
    return sum(_dur(s) for s in parts) / total * 100.0


def sched_host_share(spans) -> Optional[float]:
    """Seconds of ``serve_round`` not under its ``stream_round``, as a
    share (%) of ``serve_round`` seconds: the scheduler's own host work."""
    if not spans:
        return None
    serves = _named(spans, SERVE)
    total = sum(_dur(s) for s in serves)
    if total <= 0:
        return None
    rounds = _named(spans, ROUND)
    inner = sum(_dur(r) for r in rounds
                if any(_inside(r, s) for s in serves))
    return (total - inner) / total * 100.0


def shard_read_gbps(spans) -> Optional[float]:
    """Shard bytes the loading agents parsed per second of parsing
    (``shard_read``: ``np.load`` into host arrays), in GB/s."""
    reads = [s for s in _named(spans or (), "shard_read")
             if _dur(s) > 0]
    seconds = sum(_dur(s) for s in reads)
    if seconds <= 0:
        return None
    return sum(s[4]["bytes"] for s in reads) / seconds / 1e9


def budget_wait_share(spans) -> Optional[float]:
    """Seconds the loading agents sat in ``shard_acquire`` (the in-order
    grant and the ledger's S_stop) as a share (%) of acquire + load."""
    acq = sum(_dur(s) for s in _named(spans or (), "shard_acquire"))
    load = sum(_dur(s) for s in _named(spans or (), "shard_load"))
    if acq + load <= 0:
        return None
    return acq / (acq + load) * 100.0


def self_times(spans, names: Sequence[str]) -> Dict[str, float]:
    """Seconds under each span name not covered by a span of a later
    name in ``names`` nested in it (one thread's spans, outermost name
    first): ``compute`` less its dispatches and syncs, and so on."""
    out: Dict[str, float] = {}
    for i, name in enumerate(names):
        for s in _named(spans, name):
            kids = [c for c in spans if c[0] in names[i + 1:]
                    and _inside(c, s)]
            covered = tracemod.union([(c[2], c[3]) for c in kids])
            out[name] = out.get(name, 0.0) + _dur(s) - sum(
                e - b for b, e in covered)
    return out


def round_account(spans, main: str = "MainThread") -> Optional[dict]:
    """For the slowest and the median ``stream_round`` of ``spans``
    (every thread's): its length, the self seconds under each of the
    inference agent's spans and of its ``serve_round``, and the seconds
    of each loading-agent span that carries the round's ``stream``."""
    rounds = [r for r in _named(spans, ROUND) if r[1] == main]
    if not rounds:
        return None
    by_len = sorted(rounds, key=_dur)
    median = by_len[(len(by_len) - 1) // 2]
    out = {"rounds": len(rounds),
           "round_s_median": statistics.median(_dur(r) for r in rounds)}
    for label, r in (("slowest", by_len[-1]), ("median", median)):
        sid = r[4].get("stream")
        mine = [s for s in spans if s[1] == main and _inside(s, r)]
        serve = [s for s in _named(spans, SERVE) if _inside(r, s)]
        agents: Dict[str, float] = {}
        for s in spans:
            if (s[0] in AGENT and s[1] != main
                    and s[4].get("stream") == sid):
                agents[s[0]] = agents.get(s[0], 0.0) + _dur(s)
        account = {"stream": sid, "round_s": _dur(r),
                   "self_s": self_times(mine, INFERENCE),
                   "agents_s": agents}
        if serve:
            account["serve_round_self_s"] = _dur(serve[0]) - _dur(r)
        out[label] = account
    return out


# ---------------------------------------------------------------------------
# the program's spans in the profiler trace
# ---------------------------------------------------------------------------
def hermes_events(xplane_path) -> List[list]:
    """``[name, line, start_ns, duration_ns, stats]`` for each
    ``hermes.*`` host event of the trace, the prefix taken off; ``line``
    numbers the thread's line in the trace's host plane."""
    from jax.profiler import ProfileData

    out: List[list] = []
    pd = ProfileData.from_file(str(xplane_path))
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            out += [[e.name[len(PREFIX):], i, float(e.start_ns),
                     float(e.duration_ns), dict(e.stats)]
                    for e in line.events if e.name.startswith(PREFIX)]
    return out


def inference_line(events) -> Optional[int]:
    """The line of the inference agent: the one holding the rounds."""
    lines = [e[1] for e in events if e[0] == ROUND]
    return max(set(lines), key=lines.count) if lines else None


def name_gaps(ex: dict, events, top: int = 10) -> Optional[list]:
    """The ``top`` longest idle gaps of the trace extract ``ex``
    (``trace.extract``) inside its window, as ``[name, seconds]``.  A gap
    is named after what the host did at its midpoint: the benchmark's
    span, then ``/`` and the innermost inference-agent span, then ``@``
    and the innermost loading-agent span then open, if any."""
    win = tracemod.window_bounds(ex)
    main = inference_line(events)
    if win is None or not ex["device"] or main is None:
        return None
    lo, hi = win
    bench = [(n, s, s + d) for n, s, d in ex["host"]
             if n != tracemod.WINDOW]
    inf = [(n, s, s + d) for n, ln, s, d, _ in events if ln == main]
    agent = [(n, s, s + d) for n, ln, s, d, _ in events
             if ln != main and n in AGENT]
    gaps = []
    for evs in ex["device"].values():
        ivs = tracemod.union(tracemod.clip(
            [(s, s + d) for _, s, d in evs], lo, hi))
        edges = [lo] + [x for iv in ivs for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            mid = (s + e) / 2
            name = tracemod._innermost(bench, mid) or "outside"
            inner = tracemod._innermost(inf, mid)
            if inner:
                name += "/" + inner
            at = tracemod._innermost(agent, mid)
            if at:
                name += "@" + at
            gaps.append((name, e - s))
    gaps.sort(key=lambda g: -g[1])
    return [[n, t / 1e9] for n, t in gaps[:top]]
