"""Layer-based model partitioning (Hermes paper §III-A step ①).

A model checkpoint is pre-processed into per-layer shards on disk:

    <dir>/manifest.json
    <dir>/embed.bin          # embedding ("other layers" in the paper)
    <dir>/layer_000.bin ...  # encoder/decoder layers (the 70-95% bulk)
    <dir>/head.bin           # final norm + lm/classifier head

Each shard is one raw file: its tensors laid end to end, each at an
offset aligned to 64 bytes.  The shard's manifest row carries its
tensor table (dotted key, dtype by name, shape, offset, nbytes; a small
non-array value such as a quantized tensor's original dtype is a
``value`` entry of the table and takes no file bytes) and ``file_bytes``,
the file's size.  The manifest also records byte sizes, kinds and
per-shard dtype/scale metadata so the Pipeline Planner can reason about
the schedule without opening shards; its ``format`` names this layout
(``SHARD_FORMAT``).  Loading a shard is one real disk read: one
``readinto`` on an unbuffered file fills one fresh 64-byte-aligned host
buffer, and the arrays handed back are views into it — no parse, no
checksum, no copy per tensor.  A directory whose manifest names another
format is stale: ``load_manifest`` / ``load_shard`` refuse it, and
``ensure_partitioned`` / ``ensure_quantized`` write it anew.

``quant="int8" | "int4"`` writes per-channel-scaled integer shards
(``checkpoint/quant.py``): 2-D matmul weights are stored as integer
payload + f32 scales, 1-D params keep the checkpoint dtype, and every
manifest ``bytes`` figure is the *quantized* size — so the planner, the
engine's ledger and the KV decode floor all shrink by ~4x (int8) / ~8x
(int4) without opening a shard.  ``load_shard`` restores quantized
arrays as ``QuantizedTensor`` pytree leaves; dequantization happens
inside the jitted module fns (core/modules.py).

MoE-family checkpoints default to the **expert split**
(``expert_split=True``): each layer becomes an attention+router shard
(kind ``"layer"`` — still the pipeline stage the Loading Agents stripe)
plus ONE SHARD PER EXPERT (kind ``"expert"``, named
``layer_<i>_expert_<e>``, carrying its owning layer's ``index`` and its
``expert`` id).  Per-expert byte sizes land in the manifest so the
Pipeline Planner and the ExpertCache reason about routing-sparse
streaming without opening shards; ``requantize`` transcodes expert
shards like any other, so int8/int4 expert streaming falls out for
free.  ``expert_split=False`` keeps the paper's whole-layer shards (the
bench baseline).
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import jax
import ml_dtypes  # noqa: F401  (numpy learns dtype names such as bfloat16)
import numpy as np

from repro.checkpoint import quant as qz
from repro.models.config import DENSE, MOE, VLM, ModelConfig

# Families whose param trees use the dense layout this partitioner (and
# the engine's module fns) understand.
PARTITION_FAMILIES = (DENSE, MOE, VLM)

# the manifest's ``format``: raw shard files, tensors at 64-byte offsets
SHARD_FORMAT = "raw-align64-v1"
ALIGN = 64


class StaleCheckpointError(ValueError):
    """A checkpoint directory whose manifest names no current format."""


def _stale(path) -> StaleCheckpointError:
    return StaleCheckpointError(
        f"checkpoint {path} is not in the {SHARD_FORMAT!r} shard format "
        f"(an older store, such as .npz shards): partition it again")


def _flatten(tree, prefix="") -> Dict[str, np.ndarray]:
    out = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            out.update(_flatten(v, f"{prefix}{k}."))
    else:
        out[prefix[:-1]] = np.asarray(tree)
    return out


def _unflatten(flat: Dict[str, np.ndarray]) -> dict:
    tree: dict = {}
    for key, val in flat.items():
        parts = key.split(".")
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = val
    return tree


def _write_shard(file: Path, stored: Dict[str, object]
                 ) -> Tuple[List[dict], int]:
    """Lay ``stored``'s arrays end to end in ``file``, each at a
    64-byte-aligned offset; a string goes into the table as a ``value``.
    Returns the tensor table and the file's size."""
    table: List[dict] = []
    offset = 0
    with open(file, "wb") as f:
        for key, val in stored.items():
            if isinstance(val, str):
                table.append({"key": key, "value": str(val)})
                continue
            a = np.asarray(val)
            shape = list(a.shape)
            a = np.ascontiguousarray(a).reshape(-1)
            pad = -offset % ALIGN
            f.write(bytes(pad))
            offset += pad
            f.write(a.view(np.uint8).data)
            table.append({"key": key, "dtype": a.dtype.name, "shape": shape,
                          "offset": offset, "nbytes": int(a.nbytes)})
            offset += int(a.nbytes)
    return table, offset


def _save_shard(path: Path, name: str, flat: Dict[str, np.ndarray],
                kind: str, index: int, quant: Optional[str],
                base_dtype: str, extra: Optional[dict] = None) -> dict:
    """Write one (possibly quantized) shard and return its manifest row."""
    fp_bytes = int(sum(a.nbytes for a in flat.values()))
    stored = qz.quantize_flat(flat, quant)
    table, file_bytes = _write_shard(path / f"{name}.bin", stored)
    nbytes = int(sum(np.asarray(a).nbytes for a in stored.values()))
    row = {"name": name, "kind": kind, "index": index, "bytes": nbytes,
           "dtype": quant or base_dtype}
    if extra:
        row.update(extra)
    if quant:
        row["fp_bytes"] = fp_bytes
        row["scale_bytes"] = int(sum(
            np.asarray(a).nbytes for k, a in stored.items()
            if k.endswith(".__scale__")))
        row["n_quantized"] = sum(1 for k in stored if k.endswith(".__q__"))
    row["file_bytes"] = file_bytes
    row["tensors"] = table
    return row


def partition_and_save(params: dict, cfg: ModelConfig, path, *,
                       quant: Optional[str] = None,
                       expert_split: Optional[bool] = None) -> dict:
    """Split a dense-family param tree (stacked layers) into shards.

    ``quant`` in {None, "int8", "int4"} selects the shard precision.
    ``expert_split`` (MoE only; defaults to True for MoE families)
    splits each layer into an attention+router shard plus one shard per
    expert — the expert-streaming checkpoint layout."""
    assert quant is None or quant in qz.QUANT_SCHEMES, quant
    if cfg.family not in PARTITION_FAMILIES:
        raise ValueError(
            f"model family '{cfg.family}' ({cfg.name}) is not supported "
            f"by the layer partitioner / PIPELOAD engine; supported "
            f"families: {', '.join(PARTITION_FAMILIES)}")
    if expert_split is None:
        expert_split = cfg.family == MOE
    if expert_split and cfg.family != MOE:
        raise ValueError(
            f"expert_split needs an MoE-family config; '{cfg.name}' is "
            f"family '{cfg.family}'")
    path = Path(path)
    path.mkdir(parents=True, exist_ok=True)
    params = jax.tree.map(np.asarray, params)

    shards: List[dict] = []

    def save(name: str, tree: dict, kind: str, index: int = -1,
             extra: Optional[dict] = None):
        shards.append(_save_shard(path, name, _flatten(tree), kind, index,
                                  quant, cfg.dtype, extra))

    embed_tree = {"embed": params["embed"]}
    if "patch_proj" in params:
        embed_tree["patch_proj"] = params["patch_proj"]
    save("embed", embed_tree, "embed")

    stacked = params["layers"]
    for i in range(cfg.num_layers):
        layer = jax.tree.map(lambda a: a[i], stacked)
        if expert_split:
            moe_p = layer.pop("moe")
            layer["moe"] = {"router": moe_p["router"]}
            save(f"layer_{i:03d}", layer, "layer", i)
            for e in range(cfg.n_experts):
                ex = {k: moe_p[k][e] for k in ("w_gate", "w_up", "w_down")}
                save(f"layer_{i:03d}_expert_{e:03d}", ex, "expert", i,
                     extra={"expert": e})
        else:
            save(f"layer_{i:03d}", layer, "layer", i)

    head_tree = {"final_norm": params["final_norm"]}
    if "lm_head" in params:
        head_tree["lm_head"] = params["lm_head"]
    save("head", head_tree, "head")

    manifest = _build_manifest(cfg.name, cfg.num_layers, cfg.dtype, shards,
                               quant, expert_split=expert_split)
    (path / "manifest.json").write_text(json.dumps(manifest, indent=1))
    return manifest


def _build_manifest(model: str, num_layers: int, dtype: str,
                    shards: List[dict], quant: Optional[str], *,
                    expert_split: bool = False) -> dict:
    manifest = {
        "format": SHARD_FORMAT,
        "model": model,
        "num_layers": num_layers,
        "dtype": dtype,
        "quant": quant,
        "shards": shards,
        "total_bytes": int(sum(s["bytes"] for s in shards)),
        "layer_bytes": int(sum(s["bytes"] for s in shards
                               if s["kind"] == "layer")),
    }
    if expert_split:
        expert_rows = [s for s in shards if s["kind"] == "expert"]
        manifest["expert_split"] = True
        manifest["expert_total_bytes"] = int(sum(s["bytes"]
                                                 for s in expert_rows))
        manifest["experts_per_layer"] = (len(expert_rows) // num_layers
                                         if num_layers else 0)
    if quant:
        manifest["quant_scheme"] = qz.SCHEME
        manifest["quant_bits"] = qz.QUANT_SCHEMES[quant][0]
    return manifest


def requantize(src, dst, quant: str) -> dict:
    """Re-write a full-precision partitioned checkpoint as quantized
    shards — no model init needed, shards are transcoded one at a time
    (peak host memory = one shard).  The manifest records the source's
    byte total so ``ensure_quantized`` can detect a stale transcode."""
    assert quant in qz.QUANT_SCHEMES, quant
    src, dst = Path(src), Path(dst)
    src_man = load_manifest(src)
    if src_man.get("quant"):
        raise ValueError(
            f"requantize needs a full-precision source checkpoint; "
            f"{src} is already {src_man['quant']}")
    dst.mkdir(parents=True, exist_ok=True)
    shards = []
    for s in src_man["shards"]:
        flat = _read_flat(src, s)
        extra = {"expert": s["expert"]} if "expert" in s else None
        shards.append(_save_shard(dst, s["name"], flat, s["kind"],
                                  s["index"], quant, src_man["dtype"],
                                  extra))
    manifest = _build_manifest(src_man["model"], src_man["num_layers"],
                               src_man["dtype"], shards, quant,
                               expert_split=bool(
                                   src_man.get("expert_split")))
    manifest["source_total_bytes"] = src_man["total_bytes"]
    (dst / "manifest.json").write_text(json.dumps(manifest, indent=1))
    return manifest


def ensure_quantized(src, dst, quant: str) -> Path:
    """Transcode ``src`` into quantized shards at ``dst`` unless a
    CURRENT transcode already sits there.  "Current" means the existing
    manifest is in this store's format and carries the right ``quant``
    tag and the source fingerprint (its manifest byte total) — without
    the check, re-partitioning the source in place would leave derived
    int8/int4 shards silently serving the *old* weights."""
    src, dst = Path(src), Path(dst)
    if is_current(dst):
        dst_man = load_manifest(dst)
        src_man = load_manifest(src)
        if (dst_man.get("quant") == quant
                and dst_man.get("source_total_bytes")
                == src_man["total_bytes"]):
            return dst
    _drop_old_shards(dst)
    requantize(src, dst, quant)
    return dst


def is_current(path) -> bool:
    """True when ``path`` holds a manifest in this store's format."""
    file = Path(path) / "manifest.json"
    return (file.exists()
            and json.loads(file.read_text()).get("format") == SHARD_FORMAT)


def ensure_partitioned(path, cfg: ModelConfig,
                       make_params: Callable[[], dict], **kw) -> Path:
    """The checkpoint directory every caller that keeps one across runs
    goes through: ``path`` as it is when it holds a checkpoint in this
    store's format, else ``make_params()`` partitioned into it anew
    (``kw`` as ``partition_and_save`` takes them), the old store's
    shard files removed."""
    path = Path(path)
    if not is_current(path):
        _drop_old_shards(path)
        partition_and_save(make_params(), cfg, path, **kw)
    return path


def _drop_old_shards(path: Path) -> None:
    for f in path.glob("*.npz"):
        f.unlink()


def load_manifest(path) -> dict:
    """The manifest of ``path``; raises ``StaleCheckpointError`` for a
    directory written in another shard format."""
    manifest = json.loads((Path(path) / "manifest.json").read_text())
    if manifest.get("format") != SHARD_FORMAT:
        raise _stale(path)
    return manifest


def shard_names(manifest: dict) -> List[str]:
    return [s["name"] for s in manifest["shards"]]


def _aligned_empty(nbytes: int) -> np.ndarray:
    """A fresh uint8 host buffer of ``nbytes`` at a 64-byte-aligned
    address."""
    raw = np.empty(nbytes + ALIGN, np.uint8)
    start = -raw.ctypes.data % ALIGN
    return raw[start:start + nbytes]


def _read_flat(path, row: dict) -> Dict[str, object]:
    """One shard's flat ``{dotted key: array | value}``: one raw read
    into one fresh aligned buffer, the arrays read-only views into it.
    Fresh per shard, never reused: the device copy of the last shard's
    views may still be reading its buffer."""
    path = Path(path)
    if "tensors" not in row:
        raise _stale(path)
    buf = _aligned_empty(int(row["file_bytes"]))
    view = memoryview(buf)
    with open(path / f"{row['name']}.bin", "rb", buffering=0) as f:
        done = 0
        while done < len(buf):
            n = f.readinto(view[done:])
            if not n:
                raise EOFError(f"{f.name}: read {done} of {len(buf)} bytes")
            done += n
    buf.flags.writeable = False
    flat: Dict[str, object] = {}
    for t in row["tensors"]:
        if "value" in t:
            flat[t["key"]] = t["value"]
            continue
        lo = t["offset"]
        flat[t["key"]] = (buf[lo:lo + t["nbytes"]]
                          .view(np.dtype(t["dtype"])).reshape(t["shape"]))
    return flat


def load_shard(path, name: str, row: Optional[dict] = None) -> dict:
    """Real disk read -> nested dict of np arrays, views into one host
    buffer (quantized entries come back as QuantizedTensor leaves).
    ``row`` is the shard's manifest row; without it the manifest is
    read."""
    if row is None:
        row = next(s for s in load_manifest(path)["shards"]
                   if s["name"] == name)
    return qz.restore_tree(_unflatten(_read_flat(path, row)))
