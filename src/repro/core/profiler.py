"""Layer Profiler (Hermes §IV-1).

Measures, per shard of a partitioned checkpoint: load time (real disk ->
host -> device), compute time (jitted forward after warmup), one-token
decode time against a KV cache (feeds the generation-aware planner) and
byte size.  The profile feeds the Pipeline Planner.

Quantized checkpoints profile exactly like full-precision ones — the
shards ARE smaller on disk and the module fns DO pay the in-jit dequant
— so ``t_load``/``t_comp``/``t_decode`` are honest per-dtype
measurements, not scaled estimates.  The profile carries the manifest's
``quant``/``dtype`` tags so the Pipeline Planner can search shard dtype
jointly with the schedule (pass one profile per quantized variant as
``{dtype: profile}``).  It also carries the manifest's shard ``format``:
``t_load`` is a property of how shards are stored and read, so
``Hermes.profile()`` measures again when a kept profile's format differs.

Expert-split MoE checkpoints additionally get per-expert byte/latency
rows: every kind-``expert`` shard lands in the profile with its manifest
bytes, ``t_load`` is measured on a per-layer sample of expert shards
(disk behaviour is uniform across a layer's experts — they are
identically-shaped files) and the median fills the rest; layer
``t_comp``/``t_decode`` are measured through the expert-streamed apply
path (router -> fetch -> combine) with a warm ExpertCache, so compute
and demand-load costs stay separable for the planner.  Top-level
aggregates (``expert_bytes``, ``expert_t_load``, ``n_experts``,
``top_k``) feed ``planner.expected_unique_experts`` and the cache-size
search.
"""
from __future__ import annotations

import json
import time
from pathlib import Path
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.partition import load_manifest, load_shard
from repro.core import telemetry as _tele
from repro.core.modules import build_module_fns
from repro.core.prefetch import PrefetchRuntime
from repro.models.config import ModelConfig


def _timed_device_load(runtime: PrefetchRuntime, ckpt_dir, row: dict):
    """One disk -> host -> device shard load, timed on the shared
    prefetch runtime (the same pool the Loading Agents use, so
    ``t_load`` measures the path serving actually takes)."""
    name = row["name"]

    def _load():
        with _tele.get_tracer().span("profile_load", shard=name):
            w = jax.tree.map(jnp.asarray, load_shard(ckpt_dir, name, row))
            jax.tree.map(lambda a: a.block_until_ready(), w)
        return w
    return runtime.timed_load(_load)


def profile_model(ckpt_dir, cfg: ModelConfig, *, batch: int = 1,
                  seq: int = 128, repeats: int = 3,
                  expert_sample: int = 4) -> Dict:
    ckpt_dir = Path(ckpt_dir)
    manifest = load_manifest(ckpt_dir)
    fns = build_module_fns(cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    expert_split = bool(manifest.get("expert_split"))
    runtime = PrefetchRuntime(workers=2, name="profiler-load")
    es = None
    if expert_split:
        from repro.core.expert_stream import ExpertStreamEngine
        es = ExpertStreamEngine(ckpt_dir, manifest, cfg, fns, workers=2,
                                runtime=runtime)
    try:
        with _tele.get_tracer().span("profile_model", model=cfg.name):
            return _profile_model(ckpt_dir, cfg, manifest, fns, tokens,
                                  runtime, es, repeats=repeats,
                                  expert_sample=expert_sample, batch=batch,
                                  seq=seq)
    finally:
        if es is not None:
            es.close()
        runtime.close()


def _profile_model(ckpt_dir, cfg: ModelConfig, manifest, fns, tokens,
                   runtime: PrefetchRuntime, es, *, repeats: int,
                   expert_sample: int, batch: int, seq: int) -> Dict:
    expert_split = bool(manifest.get("expert_split"))
    profile = {"model": cfg.name, "batch": batch, "seq": seq,
               "format": manifest["format"],
               "quant": manifest.get("quant"),
               "ckpt_dtype": manifest.get("dtype", cfg.dtype),
               "expert_split": expert_split, "shards": []}
    x = None
    expert_rows = []
    expert_t_loads = []
    for shard in manifest["shards"]:
        name, kind = shard["name"], shard["kind"]
        if kind == "expert":
            # byte figures for every expert; t_load measured on a sample
            # per layer (the shards are identically-shaped files)
            row = {"name": name, "kind": kind, "bytes": shard["bytes"],
                   "index": shard["index"], "expert": shard["expert"],
                   "dtype": shard.get("dtype")}
            if shard["expert"] < expert_sample:
                t_loads = []
                for _ in range(repeats):
                    w, dt = _timed_device_load(runtime, ckpt_dir, shard)
                    t_loads.append(dt)
                row["t_load"] = float(np.median(t_loads))
                expert_t_loads.append(row["t_load"])
            expert_rows.append(row)
            profile["shards"].append(row)
            continue
        # ---- load time (disk -> device), cold-ish: re-read every repeat
        t_loads = []
        for _ in range(repeats):
            w, dt = _timed_device_load(runtime, ckpt_dir, shard)
            t_loads.append(dt)
        # ---- compute time (expert-split MoE layers run the streamed
        # router -> fetch -> combine path; the warmup call loads the
        # activated experts, so the timed repeats hit the cache and
        # measure compute, with demand-load cost modelled separately)
        if kind == "embed":
            fn = lambda w_, x_: fns["embed"](w_, tokens)
            x_in = tokens
        elif kind == "layer" and es is not None:
            fn = lambda w_, x_, nm=name: es.layer(nm, w_, x_)
            x_in = x
        elif kind == "layer":
            fn = lambda w_, x_: fns["layer"](w_, x_)
            x_in = x
        else:
            fn = lambda w_, x_: fns["head"](w_, x_)
            x_in = x
        out = fn(w, x_in)
        out.block_until_ready()          # warmup/compile
        t_comps = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            out = fn(w, x_in)
            out.block_until_ready()
            t_comps.append(time.perf_counter() - t0)
        row = {
            "name": name, "kind": kind, "bytes": shard["bytes"],
            "dtype": shard.get("dtype", manifest.get("dtype", cfg.dtype)),
            "t_load": float(np.median(t_loads)),
            "t_comp": float(np.median(t_comps)),
        }
        if kind == "layer":
            # one-token decode time for the generation-aware planner:
            # single-token step against a seq-length KV cache
            if es is not None:
                _, cache = es.layer_cache(name, w, x, seq + 1)
                step = lambda nm=name, w_=w, c=cache: es.layer_decode(
                    nm, w_, x[:, -1:], c, seq)
            else:
                _, cache = fns["layer_cache"](w, x, seq + 1)
                step = lambda w_=w, c=cache: fns["layer_decode"](
                    w_, x[:, -1:], c, seq)
            step()[0].block_until_ready()                      # compile
            t_decs = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                y, _ = step()
                y.block_until_ready()
                t_decs.append(time.perf_counter() - t0)
            row["t_decode"] = float(np.median(t_decs))
            if es is not None:
                # keep residency at ONE layer's expert union: the next
                # layer fetches its own experts, and an uncapped cache
                # would otherwise accumulate the model's whole expert
                # pool (the dominant bytes of an MoE) during profiling
                es.clear()
        if kind == "embed":
            x = out
        elif kind == "layer":
            x = out
        profile["shards"].append(row)

    layers = [s for s in profile["shards"] if s["kind"] == "layer"]
    profile["layer_t_load"] = float(np.median([s["t_load"] for s in layers]))
    profile["layer_t_comp"] = float(np.median([s["t_comp"] for s in layers]))
    profile["layer_t_decode"] = float(np.median([s["t_decode"]
                                                 for s in layers]))
    profile["layer_bytes"] = int(np.median([s["bytes"] for s in layers]))
    profile["other_bytes"] = int(sum(s["bytes"] for s in profile["shards"]
                                     if s["kind"] not in ("layer",
                                                          "expert")))
    profile["num_layers"] = len(layers)
    if expert_split:
        med_load = float(np.median(expert_t_loads)) if expert_t_loads \
            else 0.0
        for row in expert_rows:
            row.setdefault("t_load", med_load)
        profile["expert_bytes"] = int(np.median([r["bytes"]
                                                 for r in expert_rows]))
        profile["expert_t_load"] = med_load
        profile["n_experts"] = cfg.n_experts
        profile["top_k"] = cfg.top_k
    return profile


def save_profile(profile: Dict, path):
    Path(path).write_text(json.dumps(profile, indent=1))


def load_profile(path) -> Dict:
    return json.loads(Path(path).read_text())
