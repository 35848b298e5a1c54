"""Chip smoke test: serve full-width gpt2-base through PIPELOAD on one TPU.

    python chip_smoke.py

Runs the Hermes serving path (partition -> profile -> plan ->
``BatchScheduler`` over the PIPELOAD engine) through its normal entry
point, ``repro.launch.serve.run``, twice in this one process, at the full
published width of ``gpt2_base`` (24 layers, d 1024, 16 heads, d_ff 4096,
vocab 50257, fp32, ~1.4 GB of random weights from a seed).  The memory
budget is half the checkpoint's bytes, so layers really stream:

  (a) dense KV, the CLI default;
  (b) paged KV, page size 16, every prompt opening with a shared prefix,
      prompts prefilled in 32-token chunks joined into decode rounds.
      Chunked prefill is what pins the planner to its paged candidates
      (``--page-size`` alone only adds them to the search), and it runs
      the paged verify kernel beside the paged decode kernel.

Each phase serves 4 requests (64-token prompts, 16 new tokens, up to 4
in flight) and prints the resolved attention impl (it must be the Pallas
kernels), whether the compiled decode executable holds a Pallas kernel
(``tpu_custom_call``), compile seconds apart from serve seconds, the
compile cache directory, and the device's peak memory beside the
ledger's peak.  Then the served tokens and the PIPELOAD engine's prefill
logits are checked against the in-memory fp32 model built from the same
seed (``repro.models.api.build_model``).

The script exits non-zero, printing no result line, when JAX finds no
TPU, when it is run without the repository's ``src/`` beside it, or when
any phase or check fails.  Only a run where everything passed ends with
one JSON line: {"ok": true, "device": {"platform", "kind", "count"}}.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ARCH = "gpt2_base"
SEED = 0
REQUESTS, PROMPT_LEN, NEW_TOKENS, MAX_INFLIGHT = 4, 64, 16, 4
PAGE_SIZE, SHARED_PREFIX, CHUNK = 16, 32, 32
# Limit on |served - reference| logits, as a share of the reference's
# largest |logit|.  Both sides run float32 weights, but XLA on the TPU
# feeds float32 matmuls to the MXU as bfloat16 by default (8-bit
# mantissa, relative rounding 2^-9 per operand), and that rounding
# compounds over 24 layers; 2% of the logit range leaves room for it
# while a wrong layer, page or mask moves logits by O(1).
LOGIT_RTOL = 0.02
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Seconds spent in backend compiles (persistent-cache reads
    included) and cache hits, from JAX's own monitoring events."""

    def __init__(self, jax):
        self.seconds, self.hits, self.requests = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration_secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1

    def snapshot(self):
        return self.seconds, self.hits, self.requests


def fail(msg: str) -> int:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    return 1


def decode_hlo(cfg, ckpt, paged: bool) -> str:
    """Compiled text of the decode executable the phase served with: the
    same module fn the engine builds, at the served batch and cache
    shapes (so the persistent cache usually hands back the very
    executable serving compiled)."""
    import jax
    import jax.numpy as jnp

    from repro.checkpoint.partition import load_manifest, load_shard
    from repro.core.kv_pages import pages_for
    from repro.core.modules import build_module_fns

    layer = next(s["name"] for s in load_manifest(ckpt)["shards"]
                 if s["kind"] == "layer")
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    w = jax.tree.map(shape, load_shard(ckpt, layer))
    f32 = jnp.float32
    b, kv, dh = MAX_INFLIGHT, cfg.n_kv_heads, cfg.head_dim
    x = jax.ShapeDtypeStruct((b, 1, cfg.d_model), f32)
    pos = jax.ShapeDtypeStruct((b,), jnp.int32)
    fns = build_module_fns(cfg, attn_impl="auto")
    total = PROMPT_LEN + NEW_TOKENS
    if paged:
        nb = pages_for(total, PAGE_SIZE)
        leaf = jax.ShapeDtypeStruct((b * nb + 2, PAGE_SIZE, kv, dh), f32)
        tables = jax.ShapeDtypeStruct((b, nb), jnp.int32)
        lowered = fns["layer_decode_paged"].lower(
            w, x, {"k": leaf, "v": leaf}, tables, pos)
    else:
        leaf = jax.ShapeDtypeStruct((b, total, kv, dh), f32)
        lowered = fns["layer_decode"].lower(w, x, {"k": leaf, "v": leaf},
                                            pos)
    return lowered.compile().as_text()


def serve_phase(name, clock, dev, cfg, ckpt, budget_mb, *, reduced,
                **serve_kw):
    """One ``serve.run`` call; returns (row of printed facts, outputs)."""
    from repro.core.modules import resolve_attn_impl
    from repro.launch import serve

    impl = resolve_attn_impl("auto", cfg)
    c0 = clock.snapshot()
    t0 = time.perf_counter()
    outs, stats = serve.run(
        ARCH, budget_mb=budget_mb, requests=REQUESTS,
        prompt_len=PROMPT_LEN, new_tokens=NEW_TOKENS, reduced=reduced,
        max_inflight=MAX_INFLIGHT, seed=SEED, **serve_kw)
    wall = time.perf_counter() - t0
    c1 = clock.snapshot()
    hlo = decode_hlo(cfg, ckpt, paged=bool(serve_kw.get("page_size")))
    mem = dev.memory_stats() or {}
    row = {
        "phase": name, "attn_impl": impl,
        "decode_has_tpu_custom_call": "tpu_custom_call" in hlo,
        "compile_s": c1[0] - c0[0], "cache_hits": c1[1] - c0[1],
        "compile_requests": c1[2] - c0[2],
        "serve_s": stats.latency_s, "phase_wall_s": wall,
        "requests_served": stats.requests, "new_tokens": stats.new_tokens,
        "rounds": stats.rounds, "tokens_per_s": stats.tokens_per_s,
        "page_size": stats.page_size, "prefix_hit_pages":
        stats.prefix_hit_pages, "chunk_jobs": stats.chunk_jobs,
        "ledger_peak_bytes": stats.peak_bytes,
        "budget_bytes": int(budget_mb * 2**20),
        "device_peak_bytes_in_use": mem.get("peak_bytes_in_use"),
        "device_bytes_in_use": mem.get("bytes_in_use"),
    }
    print(f"chip_smoke[{name}]: {json.dumps(row)}", flush=True)
    return row, outs


def phase_errors(row, outs, on_tpu: bool, page_size: int):
    errs = []
    if row["page_size"] != page_size:
        errs.append(f"served with page size {row['page_size']}, not "
                    f"{page_size}")
    if row["attn_impl"] != "pallas":
        errs.append(f"attn impl resolved to {row['attn_impl']!r}, "
                    "not 'pallas'")
    if on_tpu and not row["decode_has_tpu_custom_call"]:
        errs.append("decode executable holds no tpu_custom_call")
    if row["requests_served"] != REQUESTS or len(outs) != REQUESTS:
        errs.append(f"served {row['requests_served']} of {REQUESTS}")
    for rid, toks in outs.items():
        if len(toks) != PROMPT_LEN + NEW_TOKENS:
            errs.append(f"req{rid}: {len(toks)} tokens, want "
                        f"{PROMPT_LEN + NEW_TOKENS}")
    if row["ledger_peak_bytes"] > row["budget_bytes"]:
        errs.append("ledger peak above the budget")
    return [f"{row['phase']}: {e}" for e in errs]


def reference_check(cfg, ckpt, budget_mb, served):
    """Served tokens and PIPELOAD prefill logits against the in-memory
    fp32 model from the same seed.  ``served`` maps phase name -> {rid:
    prompt + generated tokens}.  Returns (row, errors)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import Hermes
    from repro.launch import serve
    from repro.models.api import build_model

    api = build_model(cfg)
    params = jax.device_put(serve.init_params(cfg, SEED), jax.devices()[0])
    prefill = jax.jit(lambda p, t: api.prefill(p, {"tokens": t}))
    decode = jax.jit(api.decode)
    total = PROMPT_LEN + NEW_TOKENS
    row, errs = {"logit_rtol": LOGIT_RTOL}, []

    def teacher_forced(seqs):
        """Reference logits before each generated token, fed the served
        tokens: (B, NEW_TOKENS, V)."""
        logits, cache = prefill(params, jnp.asarray(seqs[:, :PROMPT_LEN]))
        pad = [(0, 0)] * cache["k"].ndim
        pad[2] = (0, total - PROMPT_LEN)
        cache = jax.tree.map(lambda a: jnp.pad(a, pad), cache)
        steps = [logits]
        for t in range(PROMPT_LEN, total - 1):
            logits, cache = decode(params, jnp.asarray(seqs[:, t:t + 1]),
                                   cache, jnp.int32(t))
            steps.append(logits)
        return np.stack([np.asarray(s) for s in steps], 1)

    seqs = {phase: np.stack([outs[r] for r in sorted(outs)]).astype(
        np.int32) for phase, outs in served.items()}
    refs = {phase: teacher_forced(s) for phase, s in seqs.items()}
    scale = max(float(np.abs(r).max()) for r in refs.values())
    limit = LOGIT_RTOL * scale
    for phase, ref in refs.items():
        got = seqs[phase][:, PROMPT_LEN:]
        picked = np.take_along_axis(ref, got[..., None], -1)[..., 0]
        # how far below the reference's best each served token scores:
        # a served pick may trail it by twice the per-logit limit
        shortfall = float((ref.max(-1) - picked).max())
        row[f"{phase}_greedy_agreement"] = float(
            (ref.argmax(-1) == got).mean())
        row[f"{phase}_max_logit_shortfall"] = shortfall
        if not shortfall <= 2 * limit:
            errs.append(f"{phase}: a served token scores {shortfall:.3g} "
                        f"below the reference's greedy pick (limit "
                        f"{2 * limit:.3g})")

    prompts = jnp.asarray(seqs["dense_kv"][:, :PROMPT_LEN])
    ref_prefill = np.asarray(prefill(params, prompts)[0])
    hermes = Hermes(ckpt, cfg)
    with hermes.engine(mode="pipeload", budget_bytes=int(budget_mb * 2**20),
                       num_agents=2) as eng:
        eng.warmup(REQUESTS, PROMPT_LEN)
        got_prefill, _ = eng.run_single(prompts)
    err = float(np.abs(np.asarray(got_prefill) - ref_prefill).max())
    row.update(prefill_logit_max_abs_err=err, logit_limit=limit,
               reference_max_abs_logit=scale)
    if not err <= limit:
        errs.append(f"prefill logit error {err:.3g} above limit "
                    f"{limit:.3g}")
    print(f"chip_smoke[reference]: {json.dumps(row)}", flush=True)
    return row, errs


def smoke(*, reduced: bool = False):
    """Both serving phases and the reference check; returns the errors
    found (empty when everything passed)."""
    import jax

    from repro import compile_cache
    from repro.checkpoint.partition import load_manifest
    from repro.configs import get
    from repro.launch import serve

    dev = jax.devices()[0]
    cache_dir = compile_cache.enable()
    print(f"chip_smoke: platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(jax.devices())} compile_cache={cache_dir}",
          flush=True)
    clock = CompileClock(jax)
    cfg = get(ARCH)
    if reduced:
        cfg = cfg.reduced().with_(num_layers=8)
    t0 = time.perf_counter()
    ckpt = serve.ensure_checkpoint(cfg, SEED)
    ckpt_bytes = sum(s["bytes"] for s in load_manifest(ckpt)["shards"])
    budget_mb = ckpt_bytes / 2 / 2**20
    print(f"chip_smoke: checkpoint {ckpt} ({ckpt_bytes} bytes, "
          f"{cfg.num_layers} layers, d_model {cfg.d_model}) ready in "
          f"{time.perf_counter() - t0:.2f}s; budget {budget_mb:.1f} MiB",
          flush=True)
    on_tpu = dev.platform == "tpu"
    errs, served = [], {}
    for name, kw in (("dense_kv", {}),
                     ("paged_kv", {"page_size": PAGE_SIZE,
                                   "shared_prefix": SHARED_PREFIX,
                                   "chunk_prefill": CHUNK})):
        row, outs = serve_phase(name, clock, dev, cfg, ckpt, budget_mb,
                                reduced=reduced, **kw)
        errs += phase_errors(row, outs, on_tpu, kw.get("page_size", 0))
        served[name] = outs
    _, ref_errs = reference_check(cfg, ckpt, budget_mb, served)
    return errs + ref_errs


def main() -> int:
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        return fail(f"no repro package under {src}: run from a checkout")
    sys.path.insert(0, str(src))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"needs a TPU; JAX found {devices[0].platform!r} "
                    "devices only")
    errs = smoke()
    for e in errs:
        fail(e)
    if errs:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
