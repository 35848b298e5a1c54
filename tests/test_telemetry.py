"""Telemetry suite: the zero-cost disabled path (no-op singleton
identity — the CI overhead guard), tracer recording + Chrome-trace
export structure, spans as ``hermes.*`` profiler annotations, the
``jit_compile`` listener, metrics-registry semantics (reset-in-place),
the golden structural trace over a deterministic 2-request serve
(regenerate with ``REPRO_UPDATE_GOLDEN=1``, mirroring
tests/golden/serve_slo_trace.json), how a traced serve's spans nest and
share a round's ``stream`` id, the enabled-vs-disabled token/ledger
identity property, fault-outcome surfacing in RunStats/ServeStats and
the planner drift report."""
import glob
import json
import os
import threading
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.analysis.report import drift_report, format_drift
from repro.checkpoint import partition_and_save
from repro.configs import get_config
from repro.core import BatchScheduler, PipeloadEngine
from repro.core import telemetry as tele
from repro.models.api import build_model

MAX_TOTAL = 26
GOLDEN = Path(__file__).parent / "golden" / "telemetry_trace.json"


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """3-layer toy checkpoint (same geometry as the serving suites)."""
    cfg = get_config("gpt2_base").with_(
        num_layers=3, d_model=64, n_heads=2, n_kv_heads=2, head_dim=32,
        d_ff=128, vocab_size=300, vocab_pad_to=4, remat=False)
    path = tmp_path_factory.mktemp("ckpt") / "tiny"
    api = build_model(cfg)
    partition_and_save(api.init(jax.random.PRNGKey(0)), cfg, path)
    return cfg, path


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Every test starts (and leaves) with tracing off and a zeroed
    registry — telemetry is process-global state."""
    tele.disable()
    tele.metrics().reset()
    yield
    tele.disable()
    tele.metrics().reset()


def _serve(cfg, path, *, seed=7, requests=2, prompt_len=8, new_tokens=4,
           page=5):
    """One deterministic small serve; returns per-request outputs and
    the ServeStats."""
    eng = PipeloadEngine(path, cfg, mode="pipeload", num_agents=2,
                         page_size=page)
    sched = BatchScheduler(eng, max_inflight=2, max_total_len=MAX_TOTAL,
                           page_size=page, seed=seed)
    rng = np.random.default_rng(seed)
    rids = [sched.submit(rng.integers(0, cfg.vocab_size, (prompt_len,)),
                         new_tokens) for _ in range(requests)]
    outs, stats = sched.run()
    sched.close()
    return [np.asarray(outs[r]) for r in rids], stats


# ---------------------------------------------------------------------------
# zero-cost disabled path: the no-op singletons, by identity
# ---------------------------------------------------------------------------
def test_disabled_path_allocates_nothing():
    """Disabled tracing hands out the SAME shared objects on every call:
    no span instance, no buffer append, no argument capture — the
    structural form of the "zero tracer allocations" overhead guard."""
    tr = tele.get_tracer()
    assert tr is tele.NULL_TRACER
    assert tr.enabled is False
    s1 = tr.span("shard_load", key="h.0", bytes=123)
    s2 = tr.span("compute", layer="h.1")
    assert s1 is tele.NULL_SPAN and s2 is tele.NULL_SPAN
    with s1:
        pass                                   # context protocol is a no-op
    assert tr.instant("admit", rid=0) is None
    assert tr.counter("ledger_resident_bytes", 7) is None


class _SpyNullTracer(tele.NullTracer):
    """A disabled tracer that notes every span a call site asks it for:
    a site guarded by ``tracer.enabled`` never asks (and so builds no
    argument dict)."""

    def __init__(self):
        self.asked = []

    def span(self, name: str, **args):
        self.asked.append(name)
        return super().span(name, **args)


GUARDED = ("shard_wait", "layer_dispatch", "layer_sync", "shard_read",
           "shard_put", "serve_round", "shard_acquire", "shard_load",
           "shard_destroy", "compute")


def test_disabled_path_skips_the_guarded_sites(tiny):
    """With tracing off, the per-layer, per-shard and per-round sites
    added for the profiler's timeline hand back ``NULL_SPAN`` if asked,
    and in a real serve they are never asked at all."""
    tr = tele.get_tracer()
    for name in GUARDED:
        assert tr.span(name, layer="h.0", stream=3) is tele.NULL_SPAN
    spy = _SpyNullTracer()
    tele.enable(spy)
    assert tele.get_tracer() is spy and not spy.enabled
    cfg, path = tiny
    _, stats = _serve(cfg, path, requests=1, new_tokens=2)
    assert stats.new_tokens > 0
    assert spy.asked and set(spy.asked) == {"stream_round"}


def test_enable_disable_roundtrip():
    t = tele.enable()
    assert tele.get_tracer() is t and t.enabled
    tele.disable()
    assert tele.get_tracer() is tele.NULL_TRACER
    mine = tele.Tracer()
    assert tele.enable(mine) is mine and tele.get_tracer() is mine


# ---------------------------------------------------------------------------
# tracer recording + Chrome trace-event export structure
# ---------------------------------------------------------------------------
def test_export_chrome_trace_structure(tmp_path):
    t = tele.enable()
    with t.span("alpha", n=1):
        pass
    t.instant("beta", rid=7)
    t.counter("gamma", 3)
    t.counter("gamma", 5)

    def work():
        with t.span("alpha", n=2):
            pass
    th = threading.Thread(target=work, name="w_0")
    th.start()
    th.join()

    out = tmp_path / "trace.json"
    trace = tele.export_chrome_trace(out)
    assert json.loads(out.read_text()) == trace
    evs = trace["traceEvents"]
    metas = [e for e in evs if e["ph"] == "M"]
    assert evs[:len(metas)] == metas           # metadata rows lead
    assert {e["args"]["name"] for e in metas} == {"MainThread", "w_0"}
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"alpha"}
    assert {e["tid"] for e in xs} == {e["tid"] for e in metas}
    (inst,) = [e for e in evs if e["ph"] == "i"]
    assert inst["name"] == "beta" and inst["s"] == "t"
    assert inst["args"] == {"rid": 7}
    cs = [e for e in evs if e["ph"] == "C"]
    assert [e["args"]["value"] for e in cs] == [3.0, 5.0]
    assert all(e["ts"] >= 0 and e["dur"] >= 0 if e["ph"] == "X"
               else e.get("ts", 0) >= 0 for e in evs if e["ph"] != "M")


def _hermes_events(trace_dir):
    """``hermes.*`` events of a profiler trace: (name, line, stats), a
    line being one thread's, told apart by its place in the plane."""
    from jax.profiler import ProfileData

    (xp,) = glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(xp).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                out += [(e.name, (plane.name, i), dict(e.stats))
                        for e in line.events
                        if e.name.startswith(tele.PROFILER_PREFIX)]
    return out


def test_spans_land_in_the_profiler_trace(tmp_path):
    """Under a ``jax.profiler`` session an enabled span, on the main
    thread and on a worker, is a ``hermes.<name>`` host event with its
    arguments (and those set later) as stats."""
    t = tele.enable()

    def work():
        with t.span("shard_read", key="h.1", bytes=4096, stream=2):
            pass

    with jax.profiler.trace(str(tmp_path)):
        with t.span("serve_round", round=5) as sp:
            th = threading.Thread(target=work, name="pipeload-worker_0")
            th.start()
            th.join()
            jnp.ones(3).block_until_ready()
            sp.set(rows=4, stream=2)
    evs = {name: (line, stats) for name, line, stats
           in _hermes_events(tmp_path)}
    assert evs["hermes.shard_read"][1] == {"key": "h.1", "bytes": 4096,
                                           "stream": 2}
    assert evs["hermes.serve_round"][1] == {"round": 5, "rows": 4,
                                            "stream": 2}
    assert evs["hermes.shard_read"][0] != evs["hermes.serve_round"][0]
    # the buffers hold the same spans, the late arguments included
    by = {s[0]: s for s in t.spans}
    assert by["serve_round"][4] == {"round": 5, "rows": 4, "stream": 2}
    assert by["shard_read"][1] == "pipeload-worker_0"


def test_jit_compile_span_while_enabled():
    """A backend compile records a ``jit_compile`` span on the compiling
    thread while a tracer is on, and nothing once it is off."""
    t = tele.enable()
    scale = float(np.random.default_rng().integers(1, 10 ** 9))
    jax.jit(lambda x: x * scale + 1.0)(jnp.ones(5)).block_until_ready()
    compiles = [s for s in t.spans if s[0] == "jit_compile"]
    assert compiles and compiles[-1][1] == "MainThread"
    assert all(e >= s for _, _, s, e, _ in compiles)
    tele.disable()
    n = len(t.spans)
    jax.jit(lambda x: x * scale - 1.0)(jnp.ones(5)).block_until_ready()
    assert len(t.spans) == n


def test_export_requires_enabled(tmp_path):
    with pytest.raises(ValueError, match="no active tracer"):
        tele.export_chrome_trace(tmp_path / "x.json")


# ---------------------------------------------------------------------------
# metrics registry: instruments survive reset() (call sites cache them)
# ---------------------------------------------------------------------------
def test_metrics_registry_reset_in_place():
    m = tele.metrics()
    c, g, h = m.counter("t.count"), m.gauge("t.gauge"), m.histogram("t.h")
    c.inc()
    c.inc(2)
    g.set(5)
    g.set(2)
    h.observe(1.0)
    h.observe(3.0)
    snap = m.snapshot()
    assert snap["counters"]["t.count"] == 3
    assert snap["gauges"]["t.gauge"] == {"last": 2.0, "min": 2.0,
                                         "max": 5.0, "n": 2}
    assert snap["histograms"]["t.h"]["count"] == 2
    assert snap["histograms"]["t.h"]["max"] == 3.0
    m.reset()
    assert m.counter("t.count") is c and c.value == 0
    assert m.gauge("t.gauge") is g and g.n == 0
    assert m.histogram("t.h") is h and not h.values
    assert tele.counter_values("t.count", "never.touched") == (0, 0)


def test_summary_table():
    txt = tele.summary_table({"a": 1, "longer_name": "x"}, title="t")
    lines = txt.splitlines()
    assert lines[0] == "t:"
    assert lines[1].startswith("  a") and lines[2].endswith("x")
    assert tele.summary_table({}) == "metrics: (empty)"


# ---------------------------------------------------------------------------
# golden structural trace: span/instant/counter names + thread tracks
# ---------------------------------------------------------------------------
def _track(tname: str) -> str:
    """Normalize pool thread names (``pipeload-worker_3`` →
    ``pipeload-worker``): which NUMBERED worker records a span is
    scheduling-dependent, the pool it belongs to is not."""
    stem, _, idx = tname.rpartition("_")
    return stem if stem and idx.isdigit() else tname


def test_golden_trace_structure(tiny):
    """The trace SHAPE of a deterministic 2-request paged serve is
    pinned: which span/instant/counter names fire and which thread
    tracks record them.  Timestamps stay free; the ledger counter
    series must be time-ordered and non-negative."""
    cfg, path = tiny
    tracer = tele.enable()
    try:
        _, stats = _serve(cfg, path)
    finally:
        tele.disable()
    assert stats.requests == 2 and stats.new_tokens > 0
    got = {
        # whether a jit_compile span fires depends on the process's
        # compile caches, not on the serve
        "spans": sorted({s[0] for s in tracer.spans} - {"jit_compile"}),
        "instants": sorted({i[0] for i in tracer.instants}),
        "counters": sorted({c[0] for c in tracer.counters}),
        "tracks": sorted({_track(s[1]) for s in tracer.spans}
                         | {_track(i[1]) for i in tracer.instants}),
    }
    ledger = [(t, v) for n, t, v in tracer.counters
              if n == "ledger_resident_bytes"]
    assert ledger, "ledger counter track missing"
    assert all(v >= 0 for _, v in ledger)
    assert [t for t, _ in ledger] == sorted(t for t, _ in ledger)
    if os.environ.get("REPRO_UPDATE_GOLDEN"):
        GOLDEN.parent.mkdir(exist_ok=True)
        GOLDEN.write_text(json.dumps(got, indent=1) + "\n")
        pytest.skip("golden file regenerated")
    want = json.loads(GOLDEN.read_text())
    assert got == want, (
        "telemetry trace structure drifted from "
        "tests/golden/telemetry_trace.json "
        "(intentional? REPRO_UPDATE_GOLDEN=1 to re-pin)")


def test_traced_serve_exports_loadable_json(tiny, tmp_path):
    cfg, path = tiny
    tele.enable()
    try:
        _serve(cfg, path, requests=1, new_tokens=2)
        out = tmp_path / "trace.json"
        trace = tele.export_chrome_trace(out)
    finally:
        tele.disable()
    loaded = json.loads(out.read_text())
    assert loaded["displayTimeUnit"] == "ms"
    tracks = {e["args"]["name"] for e in loaded["traceEvents"]
              if e["ph"] == "M"}
    assert {"MainThread", "pipeload-worker"} <= {_track(t) for t in tracks}
    assert any(e["ph"] == "C" and e["name"] == "ledger_resident_bytes"
               for e in loaded["traceEvents"])
    assert trace == loaded


def _inside(inner, outer) -> bool:
    return outer[2] <= inner[2] and inner[3] <= outer[3]


def test_traced_serve_spans_nest_and_share_the_round(tiny):
    """A traced paged serve: the loading agent's parse and device put lie
    inside its ``shard_load``, the inference agent's dispatch and sync
    inside ``compute``, the wait for a shard inside ``stream_round`` and
    outside ``compute``, every round inside a ``serve_round``; and one
    round's spans, on every thread, carry the same ``stream`` id."""
    cfg, path = tiny
    tracer = tele.enable()
    try:
        _serve(cfg, path, requests=2, new_tokens=3)
    finally:
        tele.disable()
    by = {}
    for s in tracer.spans:
        by.setdefault(s[0], []).append(s)

    def parent(s, name):
        found = [p for p in by[name] if p[1] == s[1] and _inside(s, p)]
        assert len(found) == 1, (s, name)
        return found[0]

    for name in ("shard_read", "shard_put"):
        # streamed loads; embed and head load once, outside any round
        streamed = [s for s in by[name] if "stream" in s[4]]
        assert streamed
        assert {s[4]["key"] for s in by[name]
                if "stream" not in s[4]} <= {"embed", "head"}
        for s in streamed:
            assert s[4] == parent(s, "shard_load")[4]
    for name in ("layer_dispatch", "layer_sync"):
        assert by[name]
        for s in by[name]:
            comp = parent(s, "compute")
            assert s[4] == comp[4]
    for s in by["shard_wait"]:
        rnd = parent(s, "stream_round")
        assert s[4]["stream"] == rnd[4]["stream"]
        assert not any(c[1] == s[1] and _inside(c, s) or _inside(s, c)
                       for c in by["compute"])
    rounds = by["stream_round"]
    assert rounds and len({r[4]["stream"] for r in rounds}) == len(rounds)
    for r in rounds:
        serve = parent(r, "serve_round")
        assert serve[4]["stream"] == r[4]["stream"]
        assert serve[4]["rows"] >= 1
    # agent spans of round s belong to round s's window: issued after the
    # round's stream opened and finished before it closed
    agent = [s for name in ("shard_acquire", "shard_load", "shard_read",
                            "shard_put") for s in by[name]
             if "stream" in s[4]]
    assert {s[4]["stream"] for s in agent} == {r[4]["stream"]
                                               for r in rounds}
    for s in agent:
        (rnd,) = [r for r in rounds if r[4]["stream"] == s[4]["stream"]]
        serve = parent(rnd, "serve_round")
        assert serve[2] <= s[2] and s[3] <= serve[3]
        assert s[1] != "MainThread"


# ---------------------------------------------------------------------------
# telemetry must not change the computation: enabled == disabled
# ---------------------------------------------------------------------------
def test_enabled_vs_disabled_identity(tiny):
    """Tokens, the policy triple sequence, page accounting and cache
    peaks are bitwise identical with tracing on and off — observability
    never steers the schedule."""
    cfg, path = tiny

    def go(enabled):
        tele.metrics().reset()
        if enabled:
            tele.enable()
        try:
            return _serve(cfg, path, seed=11, requests=3)
        finally:
            tele.disable()

    outs0, s0 = go(False)
    outs1, s1 = go(True)
    for a, b in zip(outs0, outs1):
        np.testing.assert_array_equal(a, b)
    assert [p[:3] for p in s0.policy] == [p[:3] for p in s1.policy]
    assert (s0.new_tokens, s0.rounds, s0.pages_allocated,
            s0.pool_pages_peak, s0.cache_bytes_peak) == \
           (s1.new_tokens, s1.rounds, s1.pages_allocated,
            s1.pool_pages_peak, s1.cache_bytes_peak)


# ---------------------------------------------------------------------------
# fault-injection outcomes surface in RunStats / ServeStats
# ---------------------------------------------------------------------------
def test_fault_outcomes_surface_in_serve_stats(tiny, monkeypatch):
    cfg, path = tiny
    monkeypatch.setenv("REPRO_PREFETCH_FAULT_RATE", "0.2")
    monkeypatch.setenv("REPRO_PREFETCH_FAULT_SEED", "3")
    monkeypatch.setenv("REPRO_PREFETCH_RETRIES", "6")
    _, stats = _serve(cfg, path, seed=5, requests=2)
    assert stats.retries > 0
    assert stats.faults_absorbed > 0
    assert stats.retries >= stats.faults_absorbed
    # clean run from the same (zeroed) registry reports zero
    monkeypatch.setenv("REPRO_PREFETCH_FAULT_RATE", "0")
    tele.metrics().reset()
    _, clean = _serve(cfg, path, seed=5, requests=2)
    assert clean.retries == 0 and clean.faults_absorbed == 0


def test_fault_outcomes_surface_in_run_stats(tiny, monkeypatch):
    cfg, path = tiny
    monkeypatch.setenv("REPRO_PREFETCH_FAULT_RATE", "0.2")
    monkeypatch.setenv("REPRO_PREFETCH_FAULT_SEED", "3")
    monkeypatch.setenv("REPRO_PREFETCH_RETRIES", "6")
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (1, 8)).astype(np.int32)
    eng = PipeloadEngine(path, cfg, mode="pipeload", num_agents=2)
    try:
        _, stats = eng.run_generate(toks, 3, kv_cache=True)
    finally:
        eng.close()
    assert stats.retries > 0
    assert stats.faults_absorbed > 0


# ---------------------------------------------------------------------------
# planner drift report
# ---------------------------------------------------------------------------
def test_drift_report_rows():
    plan = SimpleNamespace(predicted_ttft_s=0.5, predicted_tpot_s=0.1,
                           predicted_throughput_tps=20.0,
                           predicted_peak_bytes=1000)
    stats = SimpleNamespace(ttft_p50_s=1.0, tpot_p50_s=0.1,
                            tokens_per_s=10.0, peak_bytes=500)
    rep = drift_report(plan, stats)
    by = {r["metric"]: r for r in rep["rows"]}
    assert set(by) == {"ttft_s", "tpot_s", "throughput_tps", "peak_bytes"}
    assert by["ttft_s"]["ratio"] == pytest.approx(2.0)
    assert by["tpot_s"]["ratio"] == pytest.approx(1.0)
    assert by["throughput_tps"]["ratio"] == pytest.approx(0.5)
    assert by["peak_bytes"]["ratio"] == pytest.approx(0.5)
    txt = format_drift(rep)
    assert "ttft_s" in txt and "2.00x" in txt


def test_drift_report_handles_missing_predictions():
    rep = drift_report(SimpleNamespace(), SimpleNamespace(peak_bytes=5))
    assert all(r["ratio"] is None for r in rep["rows"])
    assert "—" in format_drift(rep)          # renders, no crash
