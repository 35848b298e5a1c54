"""CPU tests of the readers of the program's telemetry spans.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

Each span-share reader on a synthetic span set with a known answer and
on none; the loading agents' rates and the round account on synthetic
spans; gap naming from ``hermes.*`` events on a synthetic extract and on
one recorded on a TPU v5e; and a tiny traced run on the CPU whose
readers, account and profiler events all find what they read.
"""
from __future__ import annotations

import json
import time

import pytest

from test_chip_bench import HERE, ROOT, WORKLOADS, _run, tiny_cell

from chipbench import spans  # noqa: E402
from chipbench.runner import Round, checkpoint_bytes  # noqa: E402
from chipbench.spec import load_cell  # noqa: E402

SHARES = ("shard_wait_share", "layer_dispatch_share", "layer_sync_share",
          "sched_host_share")
RECORDED = HERE / "tests" / "fixtures" / "hermes_small.json"
M = "MainThread"
ms = 1e6


def _round_spans():
    """One 1 s round of stream 4 inside a 1.2 s serve_round: two layers,
    each a wait then a compute of one dispatch and one sync."""
    return [
        ("serve_round", M, 0.0, 1.2, {"round": 9, "rows": 8, "stream": 4}),
        ("stream_round", M, 0.1, 1.1, {"layers": 2, "stream": 4}),
        ("shard_wait", M, 0.1, 0.5, {"layer": "h.0", "stream": 4}),
        ("compute", M, 0.5, 0.6, {"layer": "h.0", "stream": 4}),
        ("layer_dispatch", M, 0.5, 0.52, {"layer": "h.0", "stream": 4}),
        ("layer_sync", M, 0.52, 0.58, {"layer": "h.0", "stream": 4}),
        ("shard_wait", M, 0.6, 0.9, {"layer": "h.1", "stream": 4}),
        ("compute", M, 0.9, 1.05, {"layer": "h.1", "stream": 4}),
        ("layer_dispatch", M, 0.9, 0.93, {"layer": "h.1", "stream": 4}),
        ("layer_sync", M, 0.93, 1.03, {"layer": "h.1", "stream": 4}),
        # a wait outside any round is not counted
        ("shard_wait", M, 2.0, 3.0, {"layer": "h.0", "stream": 5}),
    ]


def _agent_spans():
    w = "pipeload-worker_0"
    return [
        ("shard_acquire", w, 0.0, 0.1, {"key": "h.0", "stream": 4}),
        ("shard_load", w, 0.1, 0.5, {"key": "h.0", "stream": 4}),
        ("shard_read", w, 0.1, 0.4, {"key": "h.0", "bytes": 600_000_000,
                                     "stream": 4}),
        ("shard_put", w, 0.4, 0.5, {"key": "h.0", "bytes": 600_000_000,
                                    "stream": 4}),
        ("shard_acquire", w, 0.5, 0.6, {"key": "h.1", "stream": 4}),
        ("shard_load", w, 0.6, 0.9, {"key": "h.1", "stream": 4}),
        ("shard_read", w, 0.6, 0.8, {"key": "h.1", "bytes": 600_000_000,
                                     "stream": 4}),
        ("shard_put", w, 0.8, 0.9, {"key": "h.1", "bytes": 600_000_000,
                                    "stream": 4}),
        ("shard_load", w, 5.0, 6.0, {"key": "h.0", "stream": 7}),
    ]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_span_shares_from_synthetic_spans(workload):
    cell = load_cell(workload, ROOT)
    rounds = [Round(0.0, 1.2, 8, 0, 8, [40] * 8, [])]
    r = _run(cell, rounds, spans=_round_spans())
    want = {"shard_wait_share": 70.0, "layer_dispatch_share": 5.0,
            "layer_sync_share": 16.0, "sched_host_share": 0.2 / 1.2 * 100}
    for name, value in want.items():
        assert cell.readers[name].read(r) == pytest.approx(value)
    # the wait is part of what agent_wait_share counts as not compute
    assert cell.readers["agent_wait_share"].read(r) == pytest.approx(75.0)


@pytest.mark.parametrize("name", SHARES)
def test_span_shares_read_nothing_without_their_spans(name):
    cell = load_cell(WORKLOADS[0], ROOT)
    rounds = [Round(0.0, 1.0, 8, 0, 8, [40] * 8, [])]
    assert cell.readers[name].read(_run(cell, rounds)) is None
    # a program that records rounds and compute but not these spans
    older = [("stream_round", M, 0.0, 1.0, {"layers": 2}),
             ("compute", M, 0.1, 0.2, {"layer": "h.0"})]
    assert cell.readers[name].read(_run(cell, rounds, spans=older)) is None


def test_agent_rates_from_synthetic_spans():
    every = _round_spans() + _agent_spans()
    assert spans.shard_read_gbps(every) == pytest.approx(1.2e9 / 0.5 / 1e9)
    assert spans.budget_wait_share(every) == pytest.approx(
        0.2 / (0.2 + 1.7) * 100)
    assert spans.shard_read_gbps(_round_spans()) is None
    assert spans.budget_wait_share([]) is None
    assert spans.shard_read_gbps(None) is None


def test_round_account_from_synthetic_spans():
    every = _round_spans() + _agent_spans()
    acc = spans.round_account(every)
    assert acc["rounds"] == 1 and acc["round_s_median"] == pytest.approx(1.0)
    for label in ("slowest", "median"):
        a = acc[label]
        assert a["stream"] == 4 and a["round_s"] == pytest.approx(1.0)
        want = {"stream_round": 0.05, "shard_wait": 0.7, "compute": 0.04,
                "layer_dispatch": 0.05, "layer_sync": 0.16}
        assert a["self_s"] == pytest.approx(want)
        # stream 7's load belongs to another round
        assert a["agents_s"] == pytest.approx(
            {"shard_acquire": 0.2, "shard_load": 0.7, "shard_read": 0.5,
             "shard_put": 0.2})
        assert a["serve_round_self_s"] == pytest.approx(0.2)
    assert spans.round_account(_agent_spans()) is None


def _synthetic_extract():
    ex = {"device": {"/device:TPU:0": [["fusion.1", 0.0, 10 * ms],
                                       ["fusion.2", 90 * ms, 10 * ms]]},
          "host": [["bench.window", 0.0, 100 * ms],
                   ["bench.step", 0.0, 100 * ms]]}
    events = [
        ["stream_round", 1, 0.0, 100 * ms, {"stream": 3}],
        ["shard_wait", 1, 10 * ms, 60 * ms, {"layer": "h.1", "stream": 3}],
        ["compute", 1, 70 * ms, 30 * ms, {"layer": "h.1", "stream": 3}],
        ["layer_sync", 1, 72 * ms, 28 * ms, {"layer": "h.1", "stream": 3}],
        ["shard_load", 0, 5 * ms, 50 * ms, {"key": "h.1", "stream": 3}],
        ["shard_read", 0, 5 * ms, 45 * ms, {"key": "h.1", "stream": 3}],
    ]
    return ex, events


def test_gaps_named_from_hermes_events():
    ex, events = _synthetic_extract()
    assert spans.inference_line(events) == 1
    gaps = spans.name_gaps(ex, events)
    # the 80 ms hole at 10-90 ms: its midpoint (50 ms) is in a wait on
    # the main line while the agent parses the shard
    assert gaps == [["bench.step/shard_wait@shard_read",
                     pytest.approx(0.08)]]
    # a copy at 70 ms splits it: the later hole is in the sync, with no
    # agent span open
    ex["device"]["/device:TPU:0"].insert(1, ["copy.1", 70 * ms, 1 * ms])
    assert spans.name_gaps(ex, events) == [
        ["bench.step/shard_wait@shard_read", pytest.approx(0.06)],
        ["bench.step/layer_sync", pytest.approx(0.019)]]
    # no events of the program's, or no device ops: nothing to name
    assert spans.name_gaps(ex, []) is None
    assert spans.name_gaps(dict(ex, device={}), events) is None


def test_recorded_extract_keeps_hermes_events():
    """An extract recorded on a TPU v5e (``round_spans.py --extract``,
    gpt2_base.stream_decode, the window's first 1.5 s): the program's
    spans are host events with their arguments as stats, on the trace's
    own clock, inference and loading agents on lines of their own, and
    they name the device's idle gaps."""
    rec = json.loads(RECORDED.read_text())
    events = rec["hermes"]
    names = {e[0] for e in events}
    assert {"stream_round", "shard_wait", "compute", "layer_dispatch",
            "layer_sync", "shard_load", "shard_read",
            "shard_put"} <= names
    main = spans.inference_line(events)
    assert {e[1] for e in events if e[0] == "shard_read"} - {main}
    assert all(e[1] == main for e in events
               if e[0] in spans.INFERENCE)
    for e in events:
        if e[0] == "shard_read":
            assert e[4]["bytes"] > 0 and e[4]["key"].startswith("layer_")
        if e[0] in spans.INFERENCE or e[0] in spans.AGENT:
            assert "stream" in e[4]
    # one round's spans, on every line, share its stream id
    rounds = {e[4]["stream"] for e in events if e[0] == "stream_round"}
    assert {e[4]["stream"] for e in events
            if e[0] == "shard_read"} & rounds
    gaps = spans.name_gaps(rec, events)
    # the cut keeps only whole host events, so the window's tail, after
    # the last whole step, lies outside any
    assert gaps and all(n.startswith("bench.") or n == "outside"
                        for n, _ in gaps)
    assert sum(n == "bench.step/shard_wait@shard_read"
               for n, _ in gaps) >= 5


def _streaming_tiny_cell():
    """The tiny cell with a budget that holds one layer: every round
    streams all three, so the loading agents' spans fire."""
    cell = tiny_cell(WORKLOADS[0])
    n = checkpoint_bytes(cell.reference, cell.config)
    per_layer = (n - 4 * (2 * 64 * 2048 + 64)) // 3
    cell.cell = dict(cell.cell,
                     budget_bytes=cell.cell["budget_bytes"] - 2 * per_layer)
    return cell


def test_tiny_traced_run_reads_the_span_metrics():
    import round_spans

    res, every, ex, events = round_spans.traced_run(
        _streaming_tiny_cell(), 2 ** 31 + 5, 2.0,
        t_start=time.perf_counter(), require_accelerator=False,
        log=lambda s: None)
    assert res["correct"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(SHARES) <= set(m)
    assert m["stream_gbps"] > 0
    # the agreements a chip run is held to
    assert m["shard_wait_share"] <= m["agent_wait_share"] + 0.5
    assert (m["layer_dispatch_share"] + m["layer_sync_share"]
            <= 100 - m["agent_wait_share"] + 0.5)
    assert spans.shard_read_gbps(every) > 0
    assert 0 <= spans.budget_wait_share(every) < 100
    acc = spans.round_account(every)
    assert acc["slowest"]["agents_s"]["shard_read"] > 0
    # the CPU trace has no device plane, but the program's spans are
    # there on their own lines
    assert ex is not None and not ex["device"]
    main = spans.inference_line(events)
    assert main is not None
    assert any(e[0] == "shard_read" and e[1] != main for e in events)
    assert spans.name_gaps(ex, events) is None


def test_round_spans_without_a_chip_fails_and_prints_no_result():
    import subprocess
    import sys

    p = subprocess.run(
        [sys.executable, str(HERE / "round_spans.py"), "--workload",
         WORKLOADS[0], "--seed", "1", "--seconds", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env=dict(__import__("os").environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 3
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
