"""Shared benchmark utilities: paper-model checkpoints + result emission."""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import numpy as np

from repro.checkpoint import (ensure_partitioned, ensure_quantized,
                              load_manifest)
from repro.configs import get_config, list_paper_models
from repro.models.api import build_model

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = ROOT / "experiments" / "bench"
CKPT_ROOT = Path("/tmp/repro_bench_ckpts")

# Paper workloads (Table I), derived from the config registry: encoder
# models (BERT / ViT) run single-pass, causal decoders generate 8
# tokens.  Oversized decoders use a reduced-DEPTH clone (GPT-J: 6 of 28
# layers): per-layer bytes/latencies are exact, totals extrapolate by
# depth — recorded in every emitted row as depth_frac.
_DEPTH_CAP = {"gpt_j": 6}


def _paper_models():
    table = {}
    for name in list_paper_models():
        cfg = get_config(name)
        table[name] = {
            "layers": _DEPTH_CAP.get(name, cfg.num_layers),
            "gen": 8 if cfg.causal else 0,
        }
    return table


PAPER_MODELS = _paper_models()


def paper_cfg(name: str):
    spec = PAPER_MODELS[name]
    cfg = get_config(name)
    full_layers = cfg.num_layers
    if spec["layers"] != full_layers:
        cfg = cfg.with_(num_layers=spec["layers"])
    return cfg, full_layers


def ensure_paper_ckpt(name: str, quant: str | None = None) -> Path:
    cfg, _ = paper_cfg(name)
    path = ensure_partitioned(
        CKPT_ROOT / name, cfg,
        lambda: build_model(cfg).init(jax.random.PRNGKey(0)))
    if quant:
        return ensure_quantized(path, CKPT_ROOT / f"{name}-{quant}", quant)
    return path


def emit(rows, name: str):
    BENCH_DIR.mkdir(parents=True, exist_ok=True)
    (BENCH_DIR / f"{name}.json").write_text(
        json.dumps(rows, indent=1, default=float))


def csv_line(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.1f},{derived}"
