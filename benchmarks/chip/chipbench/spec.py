"""Finds everything a cell needs by the names ``BENCHMARK.json`` gives.

A cell (one entry of ``workloads``) names a configuration and a traffic
mix.  Each lives in a file of its own under this directory:

    configs/<config file named in BENCHMARK.json>    sizes, as run
    references/<the config's "reference">.py          plain reference
    traffic/<traffic>.json                            generator parameters
    cells/<workload>.json                             budget and limits
    metrics/<metric>.py                               one reader per metric
    peaks.json                                        chip peaks by kind

Adding a cell, a mix, a configuration or a metric adds files and
entries; no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

HERE = Path(__file__).resolve().parents[1]


def _load_module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    reference: ModuleType
    traffic: dict
    cell: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    readers: Dict[str, ModuleType]
    peaks: dict


def metric_applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: Path) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json``."""
    bench = _json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(by_name)}")
    w = by_name[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _json(root / conf["file"])
    reference = _load_module(HERE / "references" / f"{config['reference']}.py",
                             f"chipbench_ref_{config['reference']}")
    e2e = [m for m in bench["end_to_end"] if metric_applies(m, workload)]
    per_layer = [m for m in bench["per_layer"]
                 if metric_applies(m, workload)]
    readers = {m["name"]: _load_module(HERE / "metrics" / f"{m['name']}.py",
                                       f"chipbench_metric_{m['name']}")
               for m in e2e + per_layer}
    return Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"], config=config,
                reference=reference,
                traffic=_json(HERE / "traffic" / f"{w['traffic']}.json"),
                cell=_json(HERE / "cells" / f"{workload}.json"),
                end_to_end=e2e, per_layer=per_layer, readers=readers,
                peaks=_json(HERE / "peaks.json"))


def peaks_for(peaks: dict, device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device missing from the table is
    an error, never a default."""
    if device_kind not in peaks:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(known: {sorted(peaks)})")
    return peaks[device_kind]
