"""One run of one cell: set-up, a window of whole rounds, the check.

Set-up, in order (each phase timed, all of it counted in ``setup_s``):

1. ``weights``: the configuration's reference makes every weight from
   the seed on the device, in one jitted call, in the served dtype;
2. ``to_host``: the weights come to host memory once;
3. ``write``: the program's partitioner writes them as per-layer shards
   into a fresh directory under ``$TMPDIR``, and the files are synced so
   no write-back runs inside the window;
4. ``plan``: ``Hermes(ckpt, cfg).scheduler(...)`` profiles the shards,
   plans agents, pin window and rows under the cell's budget, and builds
   the paged-KV ``BatchScheduler``;
5. ``prefill``: every client submits its first request and rounds run
   until every admitted row has its first token;
6. ``warm``: one decode round, which compiles (or loads from the
   persistent cache) the decode programs at the window's batch.

The window then starts at a round boundary and ends with the first round
that finishes after ``seconds``: a whole number of rounds, and rates
divide by its own length.  A client whose request finishes sends its
next one at once (closed loop).
"""
from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

from chipbench import check
from chipbench import trace as tracemod
from chipbench.spec import Cell, peaks_for
from chipbench.traffic import ClosedLoop

HERE = Path(__file__).resolve().parents[1]
# The program's Layer Profiler result, kept in the checkout as the
# program keeps it beside a persistent checkpoint (see ``_plan``)
PROFILE_CACHE = HERE / ".profile_cache"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
KERNELS = ("paged_flash_decode",)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


class CompileCounter:
    """Backend compiles and compile requests, from JAX's monitoring
    events.  Listeners cannot be removed, so one counter serves the
    process."""

    _instance: Optional["CompileCounter"] = None

    def __init__(self):
        import jax

        self.compiles = 0
        self.requests = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _duration(self, event, duration_secs, **_):
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += duration_secs

    def _event(self, event, **_):
        if event == CACHE_REQUEST_EVENT:
            self.requests += 1

    def snapshot(self):
        return self.compiles, self.requests, self.compile_s


@dataclasses.dataclass
class Round:
    t0: float
    t1: float
    decoded: int            # rows that decoded one token
    prefilled: int          # rows admitted and prefilled
    tokens: int             # output tokens produced
    lengths: List[int]      # KV length each decoded row attended over
    prefill_lengths: List[int]   # prompt length of each prefilled row


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    cell: Cell
    chips: int
    peaks: Optional[dict]
    setup_s: float
    window_s: float
    rounds: List[Round]
    streamed_bytes: int
    spans: Optional[list] = None          # program telemetry spans
    trace: Optional[dict] = None          # trace.reduce() output

    @property
    def tokens(self) -> int:
        return sum(r.tokens for r in self.rounds)


def _fsync_tree(path: Path) -> None:
    for f in sorted(path.iterdir()):
        fd = os.open(f, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def model_config(config: dict):
    """The program's ``ModelConfig`` from the configuration file."""
    from repro.models.config import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    return ModelConfig(**{k: v for k, v in config.items() if k in fields})


def checkpoint_bytes(ref, config: dict) -> int:
    import jax

    leaves = jax.tree_util.tree_leaves(
        ref.shapes(config), is_leaf=lambda x: isinstance(x, tuple))
    itemsize = 4 if config["dtype"] == "float32" else 2
    n = 0
    for shape in leaves:
        size = 1
        for s in shape:
            size *= s
        n += size * itemsize
    return n


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_accelerator: bool = True,
        tamper: Optional[Callable] = None, control: bool = False,
        log: Callable[[str], None] = print) -> dict:
    """One run; returns the result dict (without printing it).
    ``tamper(scheduler)`` breaks the timed path for the harness's own
    tests; ``control`` also reads the controls at the same positions
    (``result["control"]``), for ``control.py``."""
    import jax
    import numpy as np

    devices = jax.devices()
    info = device_info(devices)
    if require_accelerator and info["platform"] == "cpu":
        raise NoChip(f"JAX found no accelerator, only {info}")
    if len(devices) < cell.chips:
        raise NoChip(f"the cell needs {cell.chips} chips; JAX found "
                     f"{len(devices)}")
    peaks = (peaks_for(cell.peaks, info["kind"]) if require_accelerator
             else cell.peaks.get(info["kind"]))
    counter = CompileCounter.get()
    # float32 as the configurations state it: on a TPU, JAX's default
    # runs a float32 matmul as one bfloat16 pass, and the serve path sets
    # no precision of its own
    jax.config.update("jax_default_matmul_precision", check.PRECISION)

    from repro.checkpoint.partition import partition_and_save
    from repro.core import Hermes
    from repro.core import telemetry as tele

    cfg, ref, traffic = cell.config, cell.reference, cell.traffic
    budget = int(cell.cell["budget_bytes"])
    mcfg = model_config(cfg)
    gen = ClosedLoop(traffic, cfg["vocab_size"], seed)
    total_len = gen.max_prompt + gen.output_len
    phases: Dict[str, float] = {}
    workdir = Path(tempfile.mkdtemp(prefix="chipbench-"))
    sched = None
    try:
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.setup.weights"):
            params = ref.init(cfg, seed)
            jax.block_until_ready(params)
        phases["weights"] = time.perf_counter() - t

        t = time.perf_counter()
        host = jax.device_get(params)
        del params
        phases["to_host"] = time.perf_counter() - t

        t = time.perf_counter()
        need = checkpoint_bytes(ref, cfg)
        free = shutil.disk_usage(workdir).free
        if free < need * 1.1:
            raise OSError(f"{workdir} has {free} bytes free; the checkpoint "
                          f"needs {need}")
        ckpt = workdir / "ckpt"
        partition_and_save(host, mcfg, ckpt)
        del host
        _fsync_tree(ckpt)
        phases["write"] = time.perf_counter() - t

        t = time.perf_counter()
        profile_key = f"{cell.config_name}-{need}"
        cached = _restore_profile(profile_key, ckpt)
        # chunked prefill needs paged KV, so a chunk as long as the
        # longest prompt holds the planner to its paged candidates while
        # no prompt is long enough to be cut into chunks
        sched = Hermes(ckpt, mcfg).scheduler(
            budget_bytes=budget, max_inflight=gen.n,
            prompt_len=gen.max_prompt, new_tokens=gen.output_len,
            max_total_len=total_len, page_sizes=(traffic["page_size"],),
            chunk_prefill=gen.max_prompt)
        if not cached:
            _keep_profile(profile_key, ckpt)
        if tamper is not None:
            tamper(sched)
        eng = sched.engine
        plan = {"agents": eng.m, "pin_window": eng.pin,
                "profile": "kept" if cached else "measured",
                "max_inflight": sched.max_inflight,
                "page_size": sched.page_size, "budget_bytes": budget}
        phases["plan"] = time.perf_counter() - t

        # requests by id (all, and those not yet finished), with the
        # client that sent each
        reqs, live, client_of = {}, {}, {}

        def submit(client, prompt, n_out, arrival):
            rid = sched.submit(prompt, n_out, arrival_round=arrival)
            reqs[rid] = live[rid] = next(r for r in sched.queue
                                         if r.rid == rid)
            client_of[rid] = client

        t = time.perf_counter()
        for c, prompt, n_out in gen.first_requests():
            submit(c, prompt, n_out, 0)
        with jax.profiler.TraceAnnotation("bench.setup.prefill"):
            sched.step()
            while any(r.generated == 0 for r in sched.inflight):
                sched.step()
        phases["prefill"] = time.perf_counter() - t

        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.setup.warm"):
            sched.step()
        phases["warm"] = time.perf_counter() - t
        log(f"bench.plan: {plan}")

        # the decode head's logits, kept on the device until the window
        # has closed, with (request id, served-token index) per row
        captured: list = []
        pending: list = []
        head_fn = eng.fns["head"]

        def head(w, x):
            out = head_fn(w, x)
            if pending:
                rows = pending.pop()
                if x.shape[0] == len(rows):
                    captured.append((rows, out))
            return out

        eng.fns["head"] = head
        gc.collect()
        ev0 = len(sched.events)
        c0 = counter.snapshot()
        tracer = trace_dir = None
        if trace:
            trace_dir = workdir / "trace"
            tracer = tele.enable()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # Python calls: heavy, unread
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        rounds: List[Round] = []
        live = {rid: r for rid, r in reqs.items() if rid not in sched.done}
        window = jax.profiler.TraceAnnotation("bench.window")
        window.__enter__()
        t_w0 = time.perf_counter()
        setup_s = t_w0 - t_start
        while True:
            decoders = [r for r in sched.inflight if not r.prefilling]
            lengths = [len(r.tokens) for r in decoders]
            # the round's first head call is the decode rows', in order
            pending[:] = [[(r.rid, len(r.tokens) - len(r.prompt))
                           for r in decoders]] if decoders else []
            before = {rid: r.generated for rid, r in live.items()}
            r0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.step"):
                sched.step()
            r1 = time.perf_counter()
            produced = sum(r.generated - before[rid]
                           for rid, r in live.items())
            pre = [len(r.prompt) for rid, r in live.items()
                   if before[rid] == 0 and r.generated > 0]
            rounds.append(Round(r0, r1, len(lengths), len(pre), produced,
                                lengths, pre))
            for rid in [rid for rid in sched.done if rid in live]:
                del live[rid]
                with jax.profiler.TraceAnnotation("bench.submit"):
                    submit(client_of[rid],
                           *gen.next_request(client_of[rid]), sched.round)
            if r1 - t_w0 >= seconds:
                break
        t_w1 = time.perf_counter()
        window.__exit__(None, None, None)
        eng.fns["head"] = head_fn
        pending.clear()
        window_s = rounds[-1].t1 - t_w0
        c1 = counter.snapshot()
        spans = None
        trace_red = None
        if trace:
            jax.profiler.stop_trace()
            tele.disable()
            spans = [s for s in tracer.spans
                     if s[1] == "MainThread" and s[2] >= t_w0 and s[3] <= t_w1]
        streamed = sum(eng.shards[e[2]]["bytes"]
                       for e in sched.events[ev0:] if e[1] == "load_end")
        mem = devices[0].memory_stats() or {}
        ledger_peak = sched.ledger.peak
        served = [(rid, r.prompt, r.tokens[len(r.prompt):])
                  for rid, r in reqs.items()]
        program_logits: Dict[int, list] = {}
        for rows, out in captured:
            out = np.asarray(out)
            for i, (rid, k) in enumerate(rows):
                program_logits.setdefault(rid, []).append((k, out[i]))
        captured.clear()
        attempted = len(reqs)

        if trace:
            xp = tracemod.find_xplane(trace_dir)
            if xp is not None:
                ex = tracemod.extract(xp)
                trace_red = _reduce_trace(ex, spans, t_w0)

        round_s = [r.t1 - r.t0 for r in rounds]
        log("bench.window: " + _json({
            "rounds": len(rounds), "window_s": window_s,
            "round_s": round_s,
            "round_s_quartiles": _quartiles(round_s),
            "rows_decoded": [r.decoded for r in rounds],
            "tokens": sum(r.tokens for r in rounds),
            "streamed_bytes": streamed,
            "compiles_in_window": c1[0] - c0[0],
            "compile_requests_in_window": c1[1] - c0[1],
            "setup_phases_s": phases, "setup_s": setup_s,
            "ledger_peak_bytes": ledger_peak,
            "ledger_peak_breakdown": dict(sched.ledger.peak_breakdown),
            "device_peak_bytes_in_use": mem.get("peak_bytes_in_use"),
            "device_bytes_in_use": mem.get("bytes_in_use")}))

        result_run = Run(cell=cell, chips=cell.chips, peaks=peaks,
                         setup_s=setup_s, window_s=window_s, rounds=rounds,
                         streamed_bytes=streamed, spans=spans,
                         trace=trace_red)
        # the program's state goes before the reference runs
        sched.close()
        sched = eng = None
        reqs.clear()
        gc.collect()

        t = time.perf_counter()
        g = check.compare(ref, cfg, seed, served, total_len,
                          gen.output_len, program_logits=program_logits)
        g["seconds"] = time.perf_counter() - t
        log(f"bench.reference: {_json(g)}")
        limits = cell.cell["limits"]
        checks = {
            "ledger_peak_bytes": {"value": ledger_peak, "limit": budget},
            "logit_gap": {"value": g["logit_gap"],
                          "limit": limits["logit_gap"]},
            "logit_err": {"value": g["logit_err"],
                          "limit": limits["logit_err"]},
            # every decode row of the window has its logits compared: a
            # head call the capture missed is a fault, not a narrower check
            "logits_missed": {
                "value": abs(sum(r.decoded for r in rounds)
                             - g["logits_compared"]),
                "limit": 0},
        }
        correct = check.verdict(checks)

        names = ([m["name"] for m in cell.per_layer] if trace
                 else [m["name"] for m in cell.end_to_end])
        units = {m["name"]: m["unit"] for m in cell.end_to_end
                 + cell.per_layer}
        metrics = {}
        for name in names:
            value = cell.readers[name].read(result_run)
            if value is not None:
                metrics[name] = {"value": value, "unit": units[name]}
        device = dict(info, memory_peak_bytes=mem.get("peak_bytes_in_use"))
        result = {"correct": bool(correct), "attempted": attempted,
                  "failed": 0, "metrics": metrics, "device": device}
        if trace and trace_red is not None:
            device["busy_s"] = trace_red["busy_s"]
            device["window_s"] = trace_red["window_s"]
            result["breakdown"] = {"device_ops": trace_red["device_ops"],
                                   "idle_gaps": trace_red["idle_gaps"]}
        if control:
            result["control"] = {
                c: check.compare(ref, cfg, seed, served, total_len,
                                 gen.output_len, control=c)
                for c in ("high", "bfloat16")}
            result["control"]["token_altered"] = check.compare(
                ref, cfg, seed, check.alter_one_token(served, cfg),
                total_len, gen.output_len)
        result["checks"] = checks
        return result
    finally:
        if sched is not None:
            sched.close()
        shutil.rmtree(workdir, ignore_errors=True)


def _restore_profile(key: str, ckpt: Path) -> bool:
    """Copy a profile an earlier run of this checkout measured into the
    fresh checkpoint.  ``Hermes.profile()`` caches its Layer Profiler
    result in the checkpoint directory, keyed by device kind; a deployed
    checkpoint keeps it, and only the benchmark's checkpoint is written
    anew each run (its weights come from the seed).  The profile depends
    on shard shapes and the machine, not on weight values."""
    kept = sorted(PROFILE_CACHE.glob(f"{key}.profile.*.json"))
    for f in kept:
        shutil.copy(f, ckpt / f.name[len(key) + 1:])
    return bool(kept)


def _keep_profile(key: str, ckpt: Path) -> None:
    PROFILE_CACHE.mkdir(exist_ok=True)
    for f in ckpt.glob("profile.*.json"):
        shutil.copy(f, PROFILE_CACHE / f"{key}.{f.name}")


def _reduce_trace(ex: dict, spans, t_w0: float) -> Optional[dict]:
    """Trace reduction, with the program's main-thread spans moved onto
    the trace clock through the window annotation's start."""
    win = tracemod.window_bounds(ex)
    prog = []
    if win is not None and spans:
        offset = win[0] - t_w0 * 1e9
        prog = [(n, s * 1e9 + offset, e * 1e9 + offset)
                for n, _, s, e, _ in spans]
    return tracemod.reduce(ex, kernels=KERNELS, program_spans=prog)


def _quartiles(xs):
    import statistics

    if len(xs) < 2:
        return xs
    return statistics.quantiles(xs, n=4)


def _json(obj) -> str:
    import json

    return json.dumps(obj, default=float)


def fail(msg: str) -> int:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    return 2
