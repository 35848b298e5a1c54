"""CPU tests of the chip benchmark's harness.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

They cover what needs no chip: the whole-round window arithmetic, the
trace reduction on a small recorded extract, the roofline byte and FLOP
counts at both configurations' shapes, the loaders that find files by
name, the exit without a chip, and a tiny-size run of the whole harness
(Pallas in interpret mode) whose ``correct`` holds on the program and
fails on the control and on each fault planted in the timed path.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT / "src"), str(HERE)]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from chipbench import check, trace  # noqa: E402
from chipbench.runner import Round, Run, checkpoint_bytes, run  # noqa: E402
from chipbench.spec import load_cell, peaks_for  # noqa: E402
from chipbench.traffic import ClosedLoop, prompt_lengths  # noqa: E402

WORKLOADS = ("gpt2_base.stream_decode", "yi_9b.stream_decode")
V5E = "TPU v5 lite"


# ---------------------------------------------------------------------------
# loaders and generator
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("workload", WORKLOADS)
def test_loaders_find_files_by_name(workload):
    cell = load_cell(workload, ROOT)
    assert cell.config_name == workload.split(".")[0]
    assert cell.traffic["kind"] == "closed_loop"
    assert cell.cell["budget_bytes"] > 0
    assert {"tokens_per_s", "token_gap_mean_ms", "setup_s"} <= set(
        cell.readers)
    assert all(hasattr(m, "read") for m in cell.readers.values())


def test_unknown_workload_and_device_kind_raise():
    with pytest.raises(KeyError):
        load_cell("no_such.cell", ROOT)
    peaks = json.loads((HERE / "peaks.json").read_text())
    assert peaks_for(peaks, V5E)["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for(peaks, "some other chip")


def test_checkpoint_bytes_match_the_cells():
    gpt2 = load_cell(WORKLOADS[0], ROOT)
    yi = load_cell(WORKLOADS[1], ROOT)
    assert checkpoint_bytes(gpt2.reference, gpt2.config) == 1_627_590_656
    assert checkpoint_bytes(yi.reference, yi.config) == 4_915_871_744
    assert gpt2.cell["budget_bytes"] * 2 == 1_627_590_656


def test_every_seed_sends_the_same_lengths():
    t = load_cell(WORKLOADS[0], ROOT).traffic
    assert prompt_lengths(t) == [32, 34, 37, 39, 41, 43, 46, 48]
    yi = load_cell(WORKLOADS[1], ROOT).traffic
    lens = prompt_lengths(yi)
    assert len(set(lens)) == 16 and (lens[0], lens[-1]) == (512, 1024)
    # spread evenly, off any grid: odd and prime lengths among them
    assert max(b - a for a, b in zip(lens, lens[1:])) <= 35
    assert {683, 751, 853, 887} <= set(lens)
    sets = []
    for seed in (0, 1, 2 ** 31 + 17, 2 ** 40 + 3):
        gen = ClosedLoop(t, 50257, seed)
        first = gen.first_requests()
        sets.append(sorted(len(p) for _, p, _ in first))
        assert all(int(p.max()) < 50257 for _, p, _ in first)
        # a client's next request takes the next length of the set
        nxt, _ = gen.next_request(0)
        k = gen.lengths.index(len(first[0][1]))
        assert len(nxt) == gen.lengths[(k + 1) % 8]
    assert all(s == sets[0] for s in sets)
    a = ClosedLoop(t, 50257, 5).first_requests()
    b = ClosedLoop(t, 50257, 5).first_requests()
    assert all((x[1] == y[1]).all() for x, y in zip(a, b))


# ---------------------------------------------------------------------------
# window arithmetic and metric readers
# ---------------------------------------------------------------------------
def _run(cell, rounds, **kw):
    peaks = json.loads((HERE / "peaks.json").read_text())[V5E]
    window = rounds[-1].t1 - rounds[0].t0
    return Run(cell=cell, chips=1, peaks=peaks, setup_s=12.5,
               window_s=window, rounds=rounds, streamed_bytes=3_000_000_000,
               **kw)


def test_whole_round_window_arithmetic():
    cell = load_cell(WORKLOADS[0], ROOT)
    rounds = [Round(10.0 + 1.5 * i, 11.5 + 1.5 * i, 8, 0, 8,
                    [40 + i] * 8, []) for i in range(4)]
    r = _run(cell, rounds)
    assert r.window_s == pytest.approx(6.0)
    assert cell.readers["tokens_per_s"].read(r) == pytest.approx(32 / 6.0)
    assert cell.readers["token_gap_mean_ms"].read(r) == pytest.approx(1500)
    assert cell.readers["setup_s"].read(r) == 12.5
    assert cell.readers["batch_rows"].read(r) == 8
    assert cell.readers["stream_gbps"].read(r) == pytest.approx(0.5)
    # readers of the trace and the spans read nothing without them
    for name in ("device_idle_share", "paged_decode_roofline",
                 "agent_wait_share"):
        assert cell.readers[name].read(r) is None


def test_agent_wait_share_from_spans():
    cell = load_cell(WORKLOADS[0], ROOT)
    rounds = [Round(0.0, 1.0, 8, 0, 8, [40] * 8, [])]
    spans = [("stream_round", "MainThread", 0.0, 1.0, {}),
             ("compute", "MainThread", 0.1, 0.2, {}),
             ("compute", "MainThread", 0.5, 0.55, {})]
    r = _run(cell, rounds, spans=spans)
    assert cell.readers["agent_wait_share"].read(r) == pytest.approx(85.0)


# ---------------------------------------------------------------------------
# roofline and model FLOPs at the configurations' shapes
# ---------------------------------------------------------------------------
def test_paged_decode_cost_at_both_shapes():
    gpt2 = load_cell(WORKLOADS[0], ROOT)
    yi = load_cell(WORKLOADS[1], ROOT)
    cost = gpt2.readers["paged_decode_roofline"].call_cost
    # gpt2: 16 kv heads of 64, float32; rows at 40 and 17 slots, page 16:
    # 3 + 2 pages read; q and out 2 x 2 rows x 16 x 64 x 4 bytes
    nbytes, flops = cost(gpt2.config, [40, 17], 16)
    assert nbytes == 2 * 2 * 16 * 64 * 4 + 2 * 5 * 16 * 16 * 64 * 4
    assert flops == 4 * 16 * 64 * (40 + 17)
    # yi: 4 kv heads of 128 under 32 query heads; one row at 1000 slots
    nbytes, flops = cost(yi.config, [1000], 16)
    assert nbytes == 2 * 32 * 128 * 4 + 2 * 63 * 16 * 4 * 128 * 4
    assert flops == 4 * 32 * 128 * 1000


def test_mfu_counts_at_both_shapes():
    gpt2 = load_cell(WORKLOADS[0], ROOT)
    yi = load_cell(WORKLOADS[1], ROOT)
    mfu = gpt2.readers["mfu"]
    layers, head = mfu.matmul_params(gpt2.config)
    assert layers == 24 * (4 * 1024 * 1024 + 2 * 1024 * 4096)
    assert head == 1024 * 51200
    layers, head = mfu.matmul_params(yi.config)
    assert layers == 4 * (2 * 4096 * 4096 + 2 * 4096 * 512
                          + 3 * 4096 * 11008)
    assert head == 4096 * 65536
    r = Round(0.0, 1.0, 2, 0, 2, [10, 20], [])
    att = 4 * 16 * 64 * 24 * 30
    assert mfu.window_flops(gpt2.config, [r]) == pytest.approx(
        2 * 2 * (24 * (4 * 1024 ** 2 + 8 * 1024 ** 2) + 1024 * 51200) + att)


def test_roofline_share_from_trace():
    cell = load_cell(WORKLOADS[0], ROOT)
    rounds = [Round(0.0, 1.0, 8, 0, 8, [48] * 8, []) for _ in range(2)]
    b, _ = cell.readers["paged_decode_roofline"].call_cost(
        cell.config, [48] * 8, 16)
    calls = 2 * 24
    t_min = calls * b / 819e9
    red = {"busy_s": 0.01, "window_s": 1.0,
           "kernels": {"paged_flash_decode": {"seconds": 4 * t_min,
                                              "calls": calls}}}
    r = _run(cell, rounds, trace=red)
    assert cell.readers["paged_decode_roofline"].read(r) == pytest.approx(25)
    assert cell.readers["device_idle_share"].read(r) == pytest.approx(99)


# ---------------------------------------------------------------------------
# trace reduction on a small recorded extract
# ---------------------------------------------------------------------------
def test_union_and_clip():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]


def test_reduce_synthetic_extract():
    ex = {"device": {"/device:TPU:0": [
        ["fusion.1", 100.0, 50.0], ["paged_flash_decode.1", 140.0, 30.0],
        ["copy.2", 400.0, 100.0], ["early", 0.0, 10.0]]},
        "host": [["bench.window", 90.0, 510.0],
                 ["bench.step", 95.0, 400.0]]}
    red = trace.reduce(ex, kernels=("paged_flash_decode",),
                       program_spans=[("stream_round", 180.0, 390.0)])
    # busy: [100, 170] and [400, 500] inside the window [90, 600]
    assert red["busy_s"] == pytest.approx(170e-9)
    assert red["window_s"] == pytest.approx(510e-9)
    assert red["kernels"]["paged_flash_decode"] == {"seconds": 30e-9,
                                                    "calls": 1}
    gaps = dict((n, s) for n, s in red["idle_gaps"])
    assert gaps["bench.step/stream_round"] == pytest.approx(230e-9)
    assert gaps["outside"] == pytest.approx(100e-9)
    assert red["device_ops"][0] == ["copy.2", pytest.approx(100e-9)]


def test_reduce_recorded_extract():
    """An extract kept from a traced gpt2_base run on a v5e."""
    ex = json.loads((HERE / "tests" / "fixtures" / "trace_small.json")
                    .read_text())
    red = trace.reduce(ex, kernels=("paged_flash_decode",))
    assert red is not None and red["devices"] == 1
    # the busy union never exceeds the window nor the sum of op times
    total = sum(d for evs in ex["device"].values() for _, _, d in evs)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["busy_s"] <= total / 1e9 + 1e-12
    k = red["kernels"]["paged_flash_decode"]
    assert k["calls"] > 0 and 0 < k["seconds"] <= red["busy_s"]
    assert len(red["idle_gaps"]) <= 10 and len(red["device_ops"]) <= 10
    lo, hi = trace.window_bounds(ex)
    busy = trace.union(trace.clip(
        [(s, s + d) for evs in ex["device"].values() for _, s, d in evs],
        lo, hi))
    assert red["busy_s"] == pytest.approx(sum(e - s for s, e in busy) / 1e9)


def test_no_device_ops_or_window_reads_nothing():
    assert trace.reduce({"device": {}, "host": []}) is None
    assert trace.reduce({"device": {"/device:TPU:0": [["a", 0, 1]]},
                         "host": []}) is None


# ---------------------------------------------------------------------------
# the command without a chip, and without the program
# ---------------------------------------------------------------------------
def _cmd(root: Path, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(root / "benchmarks" / "chip" / "run.py"),
         "--workload", WORKLOADS[0], "--seed", "1", "--seconds", "1",
         *extra], cwd=root, env=env, capture_output=True, text=True,
        timeout=300)


def test_rehearsal_without_a_chip_fails_and_prints_no_result():
    p = _cmd(ROOT)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_allocator_policy_applies():
    # in a process of its own: the policy is for the benchmark's process
    p = subprocess.run([sys.executable, "-c",
                        "import run; run.keep_freed_memory()"],
                       cwd=HERE, capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trial",
                                                  "__pycache__"))
    p = _cmd(tmp_path)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


# ---------------------------------------------------------------------------
# the whole harness at a tiny size, with the timed path broken underneath
# ---------------------------------------------------------------------------
def tiny_cell(workload: str, limit: float = 0.05, output_len: int = 40):
    cell = load_cell(workload, ROOT)
    gated = cell.config["gated_mlp"]
    cell.config = dict(cell.config, num_layers=3, d_model=64, n_heads=4,
                       n_kv_heads=2 if gated else 4, head_dim=16, d_ff=128,
                       vocab_size=2000, vocab_pad_to=128)
    cell.traffic = dict(cell.traffic, clients=3,
                        prompt_lens=[8, 13, 20],
                        output_len=output_len)
    shapes = cell.reference.shapes(cell.config)
    n = checkpoint_bytes(cell.reference, cell.config)
    per_layer = (n - 4 * (2 * 64 * 2048 + 64)) // 3
    tokens = -(-(20 + output_len) // 16) * 16 + 16
    kv = 3 * 3 * tokens * 2 * cell.config["n_kv_heads"] * 16 * 4
    budget = n - 3 * per_layer + 2 * per_layer + 2 * kv
    assert shapes["layers"]["attn"]["w_q"] == (3, 64, 64)
    cell.cell = dict(cell.cell, budget_bytes=budget,
                     limits={"logit_gap": limit, "logit_err": limit})
    return cell


def _tiny(cell, seconds=2.0, **kw):
    lines = []
    res = run(cell, 2 ** 31 + 99, seconds, False,
              t_start=time.perf_counter(), require_accelerator=False,
              log=lines.append, **kw)
    return res, lines


def _wrap(name, after):
    def tamper(sched):
        fn = sched.engine.fns[name]

        def broken(*args):
            return after(args, fn(*args))
        sched.engine.fns[name] = broken
    return tamper


def _roll_logits(args, logits):
    import jax.numpy as jnp
    return jnp.roll(logits, 1, axis=-1)


def _layer_unchanged(args, out):
    return args[1], out[1]


def _half_batch(args, out):
    x, h = args[1], out[0]
    keep = x.shape[0] // 2
    return h.at[keep:].set(x[keep:]), out[1]


FAULTS = {
    "token_altered": _wrap("head", _roll_logits),
    "layer_state_unchanged": _wrap("layer_decode_paged", _layer_unchanged),
    "half_batch_left_out": _wrap("layer_decode_paged", _half_batch),
}


@pytest.mark.parametrize("workload", WORKLOADS)
def test_tiny_run_is_correct(workload):
    # no row finishes inside this window, as on the chip today
    res, lines = _tiny(tiny_cell(workload, output_len=400), seconds=1.0)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 3 and res["failed"] == 0
    assert set(res["metrics"]) == {"tokens_per_s", "token_gap_mean_ms",
                                   "setup_s"}
    assert list(res)[-1] == "checks"
    window = json.loads(next(x for x in lines
                             if x.startswith("bench.window"))
                        .split(": ", 1)[1])
    assert window["rounds"] >= 1 and window["tokens"] >= 3
    assert window["rows_decoded"] == [3] * window["rounds"]
    assert window["compiles_in_window"] == 0


def test_tiny_closed_loop_replaces_finished_requests():
    res, lines = _tiny(tiny_cell(WORKLOADS[0], output_len=10))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 3
    window = json.loads(next(x for x in lines
                             if x.startswith("bench.window"))
                        .split(": ", 1)[1])
    assert max(window["rows_decoded"]) == 3


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_tiny_run_with_fault_is_not_correct(fault):
    res, _ = _tiny(tiny_cell(WORKLOADS[0]), tamper=FAULTS[fault])
    assert not res["correct"]
    assert max(res["checks"]["logit_gap"]["value"],
               res["checks"]["logit_err"]["value"]) > 0.05


def test_tiny_run_whose_logits_escape_the_check_is_not_correct():
    """A head call before the decode rows' own (another call order)
    leaves the window's logits uncompared: ``correct`` fails rather than
    compare fewer of them."""
    def extra_head_call(sched):
        eng = sched.engine
        fn = eng.fns["layer_decode_paged"]

        def early(w, x, *rest):
            eng.fns["head"](eng._resident["head"], x[:1])
            return fn(w, x, *rest)
        eng.fns["layer_decode_paged"] = early

    res, _ = _tiny(tiny_cell(WORKLOADS[0]), tamper=extra_head_call)
    assert not res["correct"]
    assert res["checks"]["logits_missed"]["value"] > 0
    assert res["checks"]["logit_gap"]["value"] <= 1e-4


def test_tiny_control_reads_above_the_program():
    """The bfloat16 control, at the same positions, ranks tokens first
    that the float32 reference puts below its best, and its logits stray
    far more than the program's.  (The CPU computes every float32 matmul
    exactly, so the three-pass control reads like the program here; it
    separates only on the TPU.)"""
    res, _ = _tiny(tiny_cell(WORKLOADS[0]), control=True)
    program = res["checks"]
    ctl = res["control"]["bfloat16"]
    assert program["logit_gap"]["value"] <= 1e-4
    assert program["logit_err"]["value"] <= 1e-4
    assert ctl["logit_err"] > 10 * max(program["logit_err"]["value"], 1e-4)
    assert ctl["tokens_compared"] == ctl["logits_compared"] > 0
    # one served token altered: logit_gap reads it, and the cell's own
    # limits through the run's verdict call it not correct
    import control
    altered = res["control"]["token_altered"]
    assert altered["logit_gap"] > 0.05 and altered["logit_err"] is None
    limits = {"logit_gap": 0.05, "logit_err": 0.05}
    checks = control.control_checks(res, altered, limits)
    assert set(checks) == {"ledger_peak_bytes", "logit_gap"}
    assert not check.verdict(checks)
    assert check.verdict(control.control_checks(res, res["control"]["high"],
                                                limits))


def test_tiny_traced_run_reads_per_layer_metrics():
    cell = tiny_cell(WORKLOADS[0])
    res = run(cell, 5, 2.0, True, t_start=time.perf_counter(),
              require_accelerator=False, log=lambda s: None)
    assert res["correct"]
    # the CPU has no device ops in the trace and no peaks: those readers
    # stay silent rather than report 0
    assert {"batch_rows", "stream_gbps", "agent_wait_share"} <= set(
        res["metrics"])
    assert "paged_decode_roofline" not in res["metrics"]
    assert "device_idle_share" not in res["metrics"]
    assert check.verdict(res["checks"])
