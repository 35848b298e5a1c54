"""Per-layer executable modules for the PIPELOAD Execution Engine.

The engine operates at shard granularity: ``embed`` -> N x ``layer`` ->
``head``.  Two generation regimes are supported:

  * **re-prefill** (the paper's §V-B2 semantics): ``layer`` is a jitted
    full-sequence forward with ``make_cache=False``; GPT decode re-runs the
    whole prefix every token.
  * **KV-cache incremental decode** (beyond-paper): ``layer_cache`` is the
    prefill that ALSO emits the layer's KV cache, padded out to
    ``total_len`` so later single-token writes are in-place updates, and
    ``layer_decode`` advances one token against that cache.  The decode
    attention can run through the Pallas flash-decoding kernel
    (``attn_impl="pallas"``, kernels/flash_decode.py) — "auto" picks it on
    TPU, the jnp online softmax elsewhere.

Quantized checkpoints (``partition_and_save(..., quant="int8"|"int4")``)
arrive as weight trees whose 2-D matmul weights are ``QuantizedTensor``
leaves.  Every module fn dequantizes those leaves *inside* its jit — the
resident form the engine's ledger accounts stays quantized, and the fp
copy of (at most) the layer currently computing is a transient XLA
temporary, destroyed with the computation.  The embedding fn takes the
gather-then-scale fast path so the fp table is never materialised for
int8.

MoE-family configs additionally get the **expert-streaming split** of the
layer forward (core/expert_stream.py drives it):

  * ``moe_router`` / ``moe_router_cache`` / ``moe_router_decode`` — the
    attention block plus the router: everything the per-layer
    attention+router shard can compute on its own.  They return the
    post-attention residual, the normed FFN input and the batch's
    normalised top-k routing ``(top_w, top_ids)`` — the engine reads
    ``top_ids`` back and demand-loads exactly those experts.
  * ``moe_combine`` — capacity-based dispatch + expert FFN + combine over
    a *subset* of experts (the round's activated union, padded with
    zero-weight experts and ``sel=-1`` slots to a fixed bucket size).
    The math is ``models/moe.py``'s ``_moe_local`` restricted to the
    selected experts: every kept (token, expert) pair lands in the same
    buffer row with the same capacity-drop rule, and unselected experts'
    rows were all-zero in the oracle anyway — so streamed outputs match
    the in-memory oracle token-for-token.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.checkpoint import quant as qz
from repro.models import attention as attn
from repro.models import common, moe
from repro.models.dense_lm import (layer_decode, layer_decode_paged,
                                   layer_prefill, layer_verify_paged)
from repro.models.config import DENSE, MOE, VLM, ModelConfig

# Families the PIPELOAD engine can execute at shard granularity.  The
# recurrent / enc-dec families have layer semantics (states, cross
# attention) the per-layer module fns do not model yet.
ENGINE_FAMILIES = (DENSE, MOE, VLM)


def check_engine_family(cfg: ModelConfig, where: str = "the PIPELOAD "
                        "engine") -> None:
    """Raise a clear error for families the engine cannot stream, instead
    of a KeyError from deep inside module construction."""
    if cfg.family not in ENGINE_FAMILIES:
        raise ValueError(
            f"model family '{cfg.family}' ({cfg.name}) is not supported "
            f"by {where}; supported families: "
            f"{', '.join(ENGINE_FAMILIES)}")


def resolve_attn_impl(attn_impl: Optional[str],
                      cfg: Optional[ModelConfig] = None) -> Optional[str]:
    """"auto" -> the autotuned per-device choice when one is installed
    (kernels/autotune.py), else Pallas kernel on TPU and jnp online
    softmax elsewhere (interpret-mode Pallas is a validation tool, not a
    fast path).  MLA decode always takes the jnp path: its latent K and V
    heads differ in width, which the Pallas decode kernels do not take —
    decided here, where callers can see it, not inside a traced fn."""
    if cfg is not None and cfg.attention == "mla":
        return None
    if attn_impl == "auto":
        from repro.kernels import ops
        tuned = ops.tuned_paged_impl()
        if tuned is not None:
            return "pallas" if tuned == "pallas" else None
        return "pallas" if jax.default_backend() == "tpu" else None
    return attn_impl


def _pad_seq(a: jax.Array, total_len: int) -> jax.Array:
    """Grow a cache leaf (B, S, ...) to (B, total_len, ...) in place-0."""
    if a.shape[1] >= total_len:
        return a
    out = jnp.zeros((a.shape[0], total_len) + a.shape[2:], a.dtype)
    return jax.lax.dynamic_update_slice_in_dim(out, a, 0, axis=1)


def build_module_fns(cfg: ModelConfig,
                     attn_impl: Optional[str] = "auto") -> Dict[str, Callable]:
    """Returns jitted {embed, layer, layer_cache, layer_decode, head}
    apply functions — plus the expert-streaming split
    (moe_router/moe_router_cache/moe_router_decode/moe_combine) for
    MoE-family configs."""
    check_engine_family(cfg)
    impl = resolve_attn_impl(attn_impl, cfg)

    @jax.jit
    def embed_apply(weights, tokens):
        emb = weights["embed"]
        if qz.is_quantized(emb):
            return emb.take_rows(tokens)
        return emb[tokens]

    @jax.jit
    def layer_apply(weights, x):
        weights = qz.dequant_tree(weights)
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        out, _, _ = layer_prefill(weights, x, cfg, None, positions,
                                  make_cache=False)
        return out

    @functools.partial(jax.jit, static_argnums=(2,))
    def layer_cache_apply(weights, x, total_len: int):
        """Prefill one layer AND capture its KV cache, padded to
        ``total_len`` slots so decode steps write in place."""
        weights = qz.dequant_tree(weights)
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        out, cache, _ = layer_prefill(weights, x, cfg, None, positions,
                                      make_cache=True)
        cache = jax.tree.map(lambda a: _pad_seq(a, total_len), cache)
        return out, cache

    @jax.jit
    def layer_decode_apply(weights, x, cache, pos):
        """One token per sequence (B, 1, D) against this layer's cache.
        ``pos`` is the global position of the new token — a scalar for the
        single-request path, or a RAGGED (B,) vector when the batch stacks
        concurrent requests whose sequences sit at different lengths (the
        continuous-batching scheduler).  Traced either way: no per-step
        recompile, and batched rounds reuse one executable per batch
        size."""
        weights = qz.dequant_tree(weights)
        out, new_cache = layer_decode(weights, x, cfg, None, cache, pos,
                                      attn_impl=impl)
        return out, new_cache

    # Paged KV decode (core/kv_pages.py): cache leaves live in fixed-size
    # page pools (P, page, ...) and each request's logical sequence is a
    # block table of page ids.  GQA without a sliding window takes the
    # dedicated path (Pallas block-table kernel under impl="pallas", no
    # densified gather); everything else (MLA, windows) gathers the
    # row's pages into the logically contiguous cache — bit-identical to
    # the dense decode over the same padded length — runs the ordinary
    # layer_decode, and scatters the one written row back into its page.
    gqa_paged = cfg.attention != "mla" and cfg.sliding_window is None

    @jax.jit
    def layer_decode_paged_apply(weights, x, pools, tables, pos):
        """One token per request against the paged cache.  ``pools`` is
        this layer's cache dict with (P, page, ...) leaves; ``tables``
        (B, NB) int32 block tables (pad short rows with page 0);
        ``pos`` (B,) ragged write positions.  The write page must be
        private (the scheduler copy-on-writes shared pages first)."""
        weights = qz.dequant_tree(weights)
        b, nb = tables.shape
        posv = jnp.asarray(pos, jnp.int32).reshape(b)
        if gqa_paged:
            return layer_decode_paged(weights, x, cfg, pools, tables,
                                      posv, attn_impl=impl)
        ps = next(iter(pools.values())).shape[1]
        cache = jax.tree.map(
            lambda a: a[tables].reshape((b, nb * ps) + a.shape[2:]), pools)
        out, new_cache = layer_decode(weights, x, cfg, None, cache, posv,
                                      attn_impl=impl)
        rows = jnp.arange(b)

        def scatter(pool_leaf, cache_leaf):
            val = cache_leaf[rows, posv]
            return pool_leaf.at[tables[rows, posv // ps],
                                posv % ps].set(val.astype(pool_leaf.dtype))

        pools = jax.tree.map(scatter, pools, new_cache)
        return out, pools

    @jax.jit
    def layer_verify_paged_apply(weights, x, pools, tables, pos):
        """Stacked W-token speculative verify against the paged cache:
        ``x`` (B, W, D) holds each request's last committed token plus
        its draft proposals, ``pos`` (B,) the cache slot of the FIRST
        stacked token.  One weight stream scores the whole window —
        query i attends slots <= pos + i, so the outputs match W
        sequential ``layer_decode_paged`` steps."""
        weights = qz.dequant_tree(weights)
        b = tables.shape[0]
        posv = jnp.asarray(pos, jnp.int32).reshape(b)
        return layer_verify_paged(weights, x, cfg, pools, tables, posv,
                                  attn_impl=impl)

    @jax.jit
    def head_apply(weights, x):
        weights = qz.dequant_tree(weights)
        h = common.rms_norm(x, weights["final_norm"], cfg.norm_eps)
        if "lm_head" in weights:
            return (h[:, -1] @ weights["lm_head"]).astype(jnp.float32)
        return h[:, -1].astype(jnp.float32)

    @jax.jit
    def head_all_apply(weights, x):
        """Full-width head: logits for EVERY stacked position (B, W, V)
        — the verify step needs the target's greedy pick at each slot
        of the speculation window, not just the last."""
        weights = qz.dequant_tree(weights)
        h = common.rms_norm(x, weights["final_norm"], cfg.norm_eps)
        if "lm_head" in weights:
            return (h @ weights["lm_head"]).astype(jnp.float32)
        return h.astype(jnp.float32)

    fns = {"embed": embed_apply, "layer": layer_apply,
           "layer_cache": layer_cache_apply,
           "layer_decode": layer_decode_apply,
           "layer_decode_paged": layer_decode_paged_apply,
           "head": head_apply, "head_all": head_all_apply}
    if gqa_paged:
        # the stacked verify path is GQA-only (no windowed/MLA variant);
        # gating the key lets callers feature-test speculation support
        fns["layer_verify_paged"] = layer_verify_paged_apply
    if cfg.family == MOE:
        fns.update(_build_moe_stream_fns(cfg, impl))
    return fns


# ===========================================================================
# Expert-streaming MoE split (core/expert_stream.py drives these)
# ===========================================================================
def _build_moe_stream_fns(cfg: ModelConfig,
                          impl: Optional[str]) -> Dict[str, Callable]:
    k, n_e = cfg.top_k, cfg.n_experts

    def _route(weights, x):
        """Post-attention residual ``x`` -> (flat FFN input, normalised
        top-k weights, expert ids) — byte-identical routing to
        ``models/moe._moe_local``."""
        h = common.rms_norm(x, weights["ffn_norm"], cfg.norm_eps)
        hf = h.reshape(-1, h.shape[-1])
        logits = hf.astype(jnp.float32) @ weights["moe"]["router"]
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_ids = jax.lax.top_k(probs, k)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
        return hf, top_w, top_ids

    def _attn_prefill(weights, x, *, make_cache):
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        h = common.rms_norm(x, weights["attn_norm"], cfg.norm_eps)
        if cfg.attention == "mla":
            a, cache = attn.mla_prefill(weights["attn"], h, cfg, None,
                                        positions, make_cache=make_cache)
        else:
            a, cache = attn.gqa_prefill(weights["attn"], h, cfg, None,
                                        positions, causal=cfg.causal,
                                        make_cache=make_cache)
        return x + a, cache

    @jax.jit
    def moe_router_apply(weights, x):
        """Full-sequence attention + router (no cache)."""
        weights = qz.dequant_tree(weights)
        xa, _ = _attn_prefill(weights, x, make_cache=False)
        hf, top_w, top_ids = _route(weights, xa)
        return xa, hf, top_w, top_ids

    @functools.partial(jax.jit, static_argnums=(2,))
    def moe_router_cache_apply(weights, x, total_len: int):
        """Cache-capturing prefill variant (pads like layer_cache)."""
        weights = qz.dequant_tree(weights)
        xa, cache = _attn_prefill(weights, x, make_cache=True)
        cache = jax.tree.map(lambda a: _pad_seq(a, total_len), cache)
        hf, top_w, top_ids = _route(weights, xa)
        return xa, cache, hf, top_w, top_ids

    @jax.jit
    def moe_router_decode_apply(weights, x, cache, pos):
        """Single-token attention against the layer cache + router.
        ``pos`` scalar or ragged (B,), as in layer_decode."""
        weights = qz.dequant_tree(weights)
        h = common.rms_norm(x, weights["attn_norm"], cfg.norm_eps)
        if cfg.attention == "mla":
            a, new_cache = attn.mla_decode(weights["attn"], h, cfg, None,
                                           cache, pos)
        else:
            a, new_cache = attn.gqa_decode(weights["attn"], h, cfg, None,
                                           cache, pos, attn_impl=impl)
        xa = x + a
        hf, top_w, top_ids = _route(weights, xa)
        return xa, new_cache, hf, top_w, top_ids

    @jax.jit
    def moe_combine_apply(experts, sel, xa, hf, top_w, top_ids):
        """Dispatch + expert FFN + combine over the round's streamed
        experts.

        ``experts`` is a tuple of per-expert weight dicts (zero-weight
        pads at the tail); ``sel`` (U,) maps each slot to its global
        expert id (-1 for pads).  The dispatch reuses the oracle's
        ``_dispatch_indices`` — same capacity, same drop rule — then
        remaps global expert rows onto the U-expert buffer."""
        ws = [qz.dequant_tree(e) for e in experts]
        wg = jnp.stack([w["w_gate"] for w in ws])
        wu = jnp.stack([w["w_up"] for w in ws])
        wd = jnp.stack([w["w_down"] for w in ws])
        u = len(ws)
        t, d = hf.shape
        cap = moe.capacity(cfg, t)
        slots = moe._dispatch_indices(top_ids, k, n_e, cap,
                                      jnp.int32(0), n_e)       # (T, K)
        # global expert id -> union slot; -1 = not streamed this round.
        # Pad sel entries scatter out of bounds (dropped), so inv[n_e]
        # — the bucket dropped pairs land in — stays -1.
        inv = jnp.full((n_e + 1,), -1, jnp.int32)
        inv = inv.at[jnp.where(sel >= 0, sel, n_e + 1)].set(
            jnp.arange(u, dtype=jnp.int32), mode="drop")
        g = jnp.minimum(slots // cap, n_e)                     # n_e = dropped
        pos_in = slots % cap
        uslot = inv[g]
        local = jnp.where((slots < n_e * cap) & (uslot >= 0),
                          uslot * cap + pos_in, u * cap)       # OOB = drop
        tok = jnp.broadcast_to(jnp.arange(t)[:, None], (t, k)).reshape(-1)
        buf = jnp.zeros((u * cap, d), hf.dtype)
        buf = buf.at[local.reshape(-1)].set(hf[tok], mode="drop")
        buf = moe._expert_ffn(buf.reshape(u, cap, d), wg, wu, wd)
        buf = buf.reshape(u * cap, d)

        def body(acc, kk):
            contrib = buf.at[local[:, kk]].get(mode="fill", fill_value=0.0)
            return acc + contrib * top_w[:, kk, None].astype(buf.dtype), None

        acc0 = (hf * 0).astype(buf.dtype) + buf[:1] * 0
        out, _ = jax.lax.scan(body, acc0, jnp.arange(k))
        return xa + out.reshape(xa.shape)

    return {"moe_router": moe_router_apply,
            "moe_router_cache": moe_router_cache_apply,
            "moe_router_decode": moe_router_decode_apply,
            "moe_combine": moe_combine_apply}
