"""The shard store: one raw file per shard, tensors at 64-byte offsets,
the tensor table in the manifest row, and one raw read into one host
buffer per load.  Bitwise round trips per dtype, zero-copy aligned
views, manifest byte figures, no zip on the serving path, and the
handling of checkpoints and profiles from an older store."""
import json
import zipfile

import jax
import ml_dtypes
import numpy as np
import pytest

from repro.checkpoint import (SHARD_FORMAT, QuantizedTensor,
                              StaleCheckpointError, ensure_partitioned,
                              ensure_quantized, is_current, load_manifest,
                              load_shard, partition_and_save, requantize)
from repro.checkpoint import partition as pt
from repro.checkpoint import quant as qz
from repro.configs import get_config
from repro.core import BatchScheduler, Hermes, PipeloadEngine
from repro.core import telemetry as tele
from repro.kernels.autotune import device_arch
from repro.models.api import build_model

QUANTS = [None, "int8", "int4"]


def _cfg(**kw):
    return get_config("gpt2_base").with_(
        num_layers=2, d_model=64, n_heads=2, n_kv_heads=2, head_dim=32,
        d_ff=128, vocab_size=64, vocab_pad_to=8, remat=False, **kw)


def _params(cfg, seed=0):
    return build_model(cfg).init(jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def ckpts(tmp_path_factory):
    """fp32 / int8 / int4 checkpoints of one small model."""
    cfg = _cfg(dtype="float32")
    root = tmp_path_factory.mktemp("fmt")
    paths = {q: root / (q or "fp32") for q in QUANTS}
    params = _params(cfg)
    partition_and_save(params, cfg, paths[None])
    for q in QUANTS[1:]:
        requantize(paths[None], paths[q], q)
    return cfg, params, paths


def _mixed_flat():
    """One array of every dtype a shard holds, odd sizes included so
    that offsets need padding."""
    rng = np.random.default_rng(0)
    w = rng.standard_normal((6, 5)).astype(np.float32)
    return {
        "a.w": w,
        "a.norm": rng.standard_normal((7,)).astype(ml_dtypes.bfloat16),
        "b.q8": rng.integers(-127, 128, (3, 5)).astype(np.int8),
        "b.q4": qz.pack_int4(rng.integers(-8, 8, (5, 3)).astype(np.int8)),
        "b.meta": np.array([4, 5, 3], np.int64),
        "c.scalar": np.float32(1.5) * np.ones((), np.float32),
        "c.empty": np.zeros((0,), np.float32),
    }


def _save_mixed(path, quant):
    path.mkdir(parents=True, exist_ok=True)
    return pt._save_shard(path, "mixed", _mixed_flat(), "layer", 0, quant,
                          "float32")


def _host_arrays(tree):
    leaves = jax.tree.leaves(tree)
    return [a for a in leaves if isinstance(a, np.ndarray)]


@pytest.mark.parametrize("quant", QUANTS)
def test_store_round_trips_bitwise(tmp_path, quant):
    row = _save_mixed(tmp_path, quant)
    got = pt._unflatten(pt._read_flat(tmp_path, row))
    want = pt._unflatten(qz.quantize_flat(_mixed_flat(), quant))
    # every stored array comes back with its dtype, shape and bytes
    flat_got = pt._read_flat(tmp_path, row)
    for key, w in qz.quantize_flat(_mixed_flat(), quant).items():
        g = flat_got[key]
        if isinstance(w, str):
            assert g == w and isinstance(g, str)
            continue
        w = np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape, key
        assert g.tobytes() == w.tobytes(), key
    tree = load_shard(tmp_path, "mixed", row)
    if quant is None:
        assert tree["a"]["norm"].dtype == ml_dtypes.bfloat16
        assert not any(isinstance(v, QuantizedTensor)
                       for v in jax.tree.leaves(
                           tree, is_leaf=qz.is_quantized))
    else:
        qt = tree["a"]["w"]
        assert isinstance(qt, QuantizedTensor)
        assert qt.dtype == "float32" and qt.shape == (6, 5)
        assert qt.bits == qz.QUANT_SCHEMES[quant][0]
        ref = qz.quantize_array(_mixed_flat()["a.w"], quant)
        assert np.asarray(qt.q).tobytes() == np.asarray(ref.q).tobytes()
        assert np.asarray(qt.scale).tobytes() == \
            np.asarray(ref.scale).tobytes()
        # the original dtype is a table value, not file bytes
        dt = [t for t in row["tensors"] if t["key"] == "a.w.__dtype__"]
        assert dt == [{"key": "a.w.__dtype__", "value": "float32"}]
    assert set(got) == set(want)


def test_bfloat16_checkpoint_comes_back_as_bfloat16(tmp_path):
    cfg = _cfg(dtype="bfloat16")
    params = _params(cfg)
    partition_and_save(params, cfg, tmp_path / "bf16")
    got = load_shard(tmp_path / "bf16", "layer_001")
    want = jax.tree.map(lambda a: np.asarray(a[1]), params["layers"])
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert w.dtype == ml_dtypes.bfloat16
        assert g.dtype == ml_dtypes.bfloat16
        assert g.tobytes() == w.tobytes()


@pytest.mark.parametrize("quant", QUANTS)
def test_arrays_are_aligned_views_of_one_buffer(ckpts, quant):
    _, _, paths = ckpts
    row = next(s for s in load_manifest(paths[quant])["shards"]
               if s["name"] == "layer_000")
    flat = pt._read_flat(paths[quant], row)
    arrays = [a for a in flat.values() if isinstance(a, np.ndarray)]
    assert len(arrays) == sum(1 for t in row["tensors"]
                              if "value" not in t)
    # load_shard hands on views too, not copies
    served = _host_arrays(load_shard(paths[quant], "layer_000", row))
    assert served and all(a.base is not None for a in served)
    owners = {id(a.base) for a in arrays}
    assert len(owners) == 1
    base = arrays[0].base
    lo = base.__array_interface__["data"][0]
    hi = lo + base.nbytes
    for a in arrays:
        addr = a.__array_interface__["data"][0]
        assert a.flags.c_contiguous
        assert addr % pt.ALIGN == 0
        assert lo <= addr and addr + a.nbytes <= hi
        assert not a.flags.writeable


def test_each_load_reads_into_a_fresh_buffer(ckpts):
    _, _, paths = ckpts
    a = _host_arrays(load_shard(paths[None], "layer_000"))[0]
    b = _host_arrays(load_shard(paths[None], "layer_000"))[0]
    assert not np.shares_memory(a, b)
    assert a.tobytes() == b.tobytes()


@pytest.mark.parametrize("quant", QUANTS)
def test_manifest_bytes_are_tensor_bytes(ckpts, quant):
    cfg, params, paths = ckpts
    man = load_manifest(paths[quant])
    assert man["format"] == SHARD_FORMAT
    layer = jax.tree.map(lambda a: np.asarray(a[0]), params["layers"])
    flat = pt._flatten(layer)
    stored = qz.quantize_flat(flat, quant)
    row = next(s for s in man["shards"] if s["name"] == "layer_000")
    assert row["bytes"] == sum(np.asarray(a).nbytes
                               for a in stored.values())
    # padding is file bytes, never manifest bytes
    table = sum(t["nbytes"] for t in row["tensors"] if "value" not in t)
    strings = sum(np.asarray(np.str_(t["value"])).nbytes
                  for t in row["tensors"] if "value" in t)
    assert row["bytes"] == table + strings
    assert (paths[quant] / "layer_000.bin").stat().st_size \
        == row["file_bytes"] >= table
    if quant is None:
        assert row["bytes"] == sum(a.nbytes for a in flat.values())
    assert man["total_bytes"] == sum(s["bytes"] for s in man["shards"])


def test_paged_serving_round_opens_no_zip(ckpts, monkeypatch):
    cfg, _, paths = ckpts
    opened = []

    def spy(name):
        def refuse(*a, **k):
            opened.append(name)
            raise AssertionError(f"{name} called on the serving path")
        return refuse

    monkeypatch.setattr(np, "load", spy("np.load"))
    monkeypatch.setattr(zipfile, "ZipFile", spy("zipfile.ZipFile"))
    eng = PipeloadEngine(paths[None], cfg, mode="pipeload", num_agents=2,
                         page_size=4)
    try:
        sched = BatchScheduler(eng, max_inflight=2, max_total_len=16)
        rng = np.random.default_rng(3)
        for n in (6, 9):
            sched.submit(rng.integers(0, 64, (n,)), 2)
        outs, stats = sched.run()
    finally:
        eng.close()
    assert opened == []
    assert len(outs) == 2 and stats.new_tokens == 4
    assert not list(paths[None].glob("*.npz"))


def test_loader_counters_count_every_read(ckpts):
    cfg, _, paths = ckpts
    reg = tele.metrics()
    eng = PipeloadEngine(paths[None], cfg, mode="pipeload", num_agents=1)
    try:
        want = eng.shards["layer_000"]["bytes"]
        for traced in (False, True):
            b0, s0 = tele.counter_values("loader.shard_read_bytes",
                                         "loader.shard_read_s")
            if traced:
                tele.enable()
            try:
                eng._load("layer_000")
            finally:
                tele.disable()
            b1, s1 = tele.counter_values("loader.shard_read_bytes",
                                         "loader.shard_read_s")
            assert b1 - b0 == want
            assert s1 > s0
    finally:
        eng.close()
    assert "loader.shard_read_bytes" in reg.snapshot()["counters"]


# ---------------------------------------------------------------------------
# checkpoints and profiles from an older store
# ---------------------------------------------------------------------------
def _make_npz_era(path):
    """Turn a current checkpoint into what the older store wrote: one
    .npz per shard and a manifest with no format and no tensor tables."""
    man = json.loads((path / "manifest.json").read_text())
    for row in man["shards"]:
        flat = pt._read_flat(path, row)
        np.savez(path / f"{row['name']}.npz",
                 **{k: (np.str_(v) if isinstance(v, str) else v)
                    for k, v in flat.items()})
        (path / f"{row['name']}.bin").unlink()
        del row["tensors"], row["file_bytes"]
    del man["format"]
    (path / "manifest.json").write_text(json.dumps(man))


@pytest.fixture
def stale(tmp_path):
    cfg = _cfg(dtype="float32")
    path = tmp_path / "old"
    partition_and_save(_params(cfg), cfg, path)
    _make_npz_era(path)
    return cfg, path


def test_stale_checkpoint_is_refused_by_name(stale):
    _, path = stale
    assert not is_current(path)
    with pytest.raises(StaleCheckpointError, match=str(path)):
        load_manifest(path)
    with pytest.raises(StaleCheckpointError, match=str(path)):
        load_shard(path, "layer_000")
    row = json.loads((path / "manifest.json").read_text())["shards"][1]
    with pytest.raises(StaleCheckpointError, match=str(path)):
        load_shard(path, row["name"], row)


def test_ensure_partitioned_rewrites_a_stale_directory(stale):
    cfg, path = stale
    made = []

    def make():
        made.append(1)
        return _params(cfg, seed=1)

    assert ensure_partitioned(path, cfg, make) == path
    assert made == [1] and is_current(path)
    assert not list(path.glob("*.npz"))
    load_shard(path, "layer_000")
    # current: kept as it is, no new weights made
    ensure_partitioned(path, cfg, make)
    assert made == [1]


def test_serve_checkpoint_cache_rewrites_a_stale_directory(stale,
                                                           monkeypatch):
    from repro.launch import serve

    cfg, path = stale
    monkeypatch.setattr(serve, "CKPT_ROOT", path.parent)
    cfg = cfg.with_(name=path.name)
    assert serve.ensure_checkpoint(cfg) == path
    assert is_current(path)
    want = jax.tree.map(lambda a: np.asarray(a[0]),
                        serve.init_params(cfg)["layers"])
    got = load_shard(path, "layer_000")
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.tobytes() == w.tobytes()


def test_ensure_quantized_rewrites_a_stale_variant(ckpts, stale):
    cfg, _, paths = ckpts
    _, dst = stale
    (dst / "manifest.json").write_text(json.dumps(
        {**json.loads((dst / "manifest.json").read_text()),
         "quant": "int8"}))
    ensure_quantized(paths[None], dst, "int8")
    man = load_manifest(dst)
    assert man["quant"] == "int8"
    assert man["source_total_bytes"] == load_manifest(paths[None])[
        "total_bytes"]
    assert not list(dst.glob("*.npz"))
    assert isinstance(load_shard(dst, "layer_000")["attn"]["w_q"],
                      QuantizedTensor)


@pytest.mark.parametrize("kept_format,measured", [
    (SHARD_FORMAT, False), ("npz", True), (None, True)])
def test_kept_profile_of_another_format_is_measured_again(
        ckpts, tmp_path, monkeypatch, kept_format, measured):
    import shutil

    from repro.core import hermes as hermes_mod

    cfg, _, paths = ckpts
    ckpt = tmp_path / "ck"
    shutil.copytree(paths[None], ckpt)
    kept = {"layer_t_load": 123.0}
    if kept_format is not None:
        kept["format"] = kept_format
    (ckpt / f"profile.{device_arch()}.json").write_text(json.dumps(kept))
    calls = []

    def fake_profile(d, c, **kw):
        calls.append(d)
        return {"format": load_manifest(d)["format"], "layer_t_load": 1.0}

    monkeypatch.setattr(hermes_mod, "profile_model", fake_profile)
    prof = Hermes(ckpt, cfg).profile()
    assert bool(calls) == measured
    assert prof["layer_t_load"] == (1.0 if measured else 123.0)
    if measured:
        again = json.loads(
            (ckpt / f"profile.{device_arch()}.json").read_text())
        assert again["format"] == SHARD_FORMAT


def test_profiler_records_the_shard_format(ckpts):
    from repro.core.profiler import profile_model

    cfg, _, paths = ckpts
    prof = profile_model(paths["int8"], cfg, seq=8, repeats=1)
    assert prof["format"] == SHARD_FORMAT
