"""Attention: GQA (full / sliding-window) and MLA, prefill + decode paths.

Prefill/train uses a chunked online-softmax ("flash in jnp") so the (S, S)
score matrix is never materialised — required for the 32k prefill shape.

Decode uses flash-decoding with the KV cache sharded on the *sequence*
dimension across the ``model`` mesh axis: every shard attends over its cache
chunk and the per-shard partial (o, m, l) statistics are combined with one
small all-gather.  This is uniform in kv_heads, so any GQA geometry shards
over a 16-wide model axis without divisibility constraints.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models import common
from repro.models.config import ModelConfig
from repro.sharding import ShardingCtx, constrain

NEG_INF = -1e30


# ===========================================================================
# Parameter initialisation
# ===========================================================================
def gqa_init(key, cfg: ModelConfig, d_model: Optional[int] = None) -> dict:
    d = d_model or cfg.d_model
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = common.split_keys(key, 4)
    p = {
        "w_q": common.dense_init(ks[0], d, h * dh, cfg.jnp_dtype),
        "w_k": common.dense_init(ks[1], d, kv * dh, cfg.jnp_dtype),
        "w_v": common.dense_init(ks[2], d, kv * dh, cfg.jnp_dtype),
        "w_o": common.dense_init(ks[3], h * dh, d, cfg.jnp_dtype),
    }
    if cfg.qkv_bias:
        p["b_q"] = common.zeros((h * dh,), cfg.jnp_dtype)
        p["b_k"] = common.zeros((kv * dh,), cfg.jnp_dtype)
        p["b_v"] = common.zeros((kv * dh,), cfg.jnp_dtype)
    return p


def mla_init(key, cfg: ModelConfig) -> dict:
    d, h = cfg.d_model, cfg.n_heads
    dq, dc = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.v_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    ks = common.split_keys(key, 8)
    return {
        "w_dq": common.dense_init(ks[0], d, dq, cfg.jnp_dtype),
        "q_norm": common.ones((dq,), cfg.jnp_dtype),
        "w_uq_nope": common.dense_init(ks[1], dq, h * dn, cfg.jnp_dtype),
        "w_uq_rope": common.dense_init(ks[2], dq, h * dr, cfg.jnp_dtype),
        "w_dkv": common.dense_init(ks[3], d, dc, cfg.jnp_dtype),
        "kv_norm": common.ones((dc,), cfg.jnp_dtype),
        "w_kr": common.dense_init(ks[4], d, dr, cfg.jnp_dtype),
        "w_uk": common.dense_init(ks[5], dc, h * dn, cfg.jnp_dtype),
        "w_uv": common.dense_init(ks[6], dc, h * dv, cfg.jnp_dtype),
        "w_o": common.dense_init(ks[7], h * dv, d, cfg.jnp_dtype),
    }


def attn_init(key, cfg: ModelConfig) -> dict:
    return mla_init(key, cfg) if cfg.attention == "mla" else gqa_init(key, cfg)


# ===========================================================================
# Chunked online-softmax attention (prefill / train)
#
# ``chunked_attention`` carries a flash-attention custom VJP: the backward
# pass recomputes per-block attention probabilities from saved (out, lse)
# instead of letting scan-of-scan autodiff store every (bq, bk) probability
# block — without it, a 4k-train layer keeps O(S^2) f32 residuals alive and
# no long-context shape fits HBM.
# ===========================================================================
def _pick_block(s: int, want: int) -> int:
    b = min(s, want)
    while s % b:
        b //= 2
    return max(b, 1)


def _block_bias(q_ids, k_ids, causal: bool, window: Optional[int]):
    """Additive (bq, bk) mask bias, or None when nothing is masked."""
    if not causal and window is None:
        return None
    ok = jnp.ones((q_ids.shape[0], k_ids.shape[0]), bool)
    if causal:
        ok &= k_ids[None, :] <= q_ids[:, None]
    if window is not None:
        ok &= k_ids[None, :] > q_ids[:, None] - window
    return jnp.where(ok, 0.0, NEG_INF)


def chunked_attention(q, k, v, *, causal: bool = True, q_offset=0,
                      window: Optional[int] = None,
                      valid_len: Optional[jax.Array] = None,
                      block_q: int = 512, block_k: int = 512) -> jax.Array:
    """q: (B,Sq,KV,G,dhq)  k: (B,Sk,KV,dhk)  v: (B,Sk,KV,dhv) -> (B,Sq,KV,G,dhv).

    Online softmax over kv blocks; outer sequential map over q blocks keeps
    the peak score tensor at (B,KV,G,Bq,Bk).  Differentiable via a flash
    custom VJP (valid_len is a non-differentiable inference-only extra).
    """
    if valid_len is None:
        f = _flash_fn(causal, window, int(q_offset), block_q, block_k)
        return f(q, k, v)
    return _masked_attention_fallback(q, k, v, causal=causal,
                                      q_offset=q_offset, window=window,
                                      valid_len=valid_len, block_q=block_q,
                                      block_k=block_k)


def _masked_attention_fallback(q, k, v, *, causal, q_offset, window,
                               valid_len, block_q, block_k) -> jax.Array:
    """Original (non-custom-vjp) path, used only with ``valid_len``."""
    b, sq, kvh, g, dhq = q.shape
    sk, dhv = k.shape[1], v.shape[-1]
    bq, bk = _pick_block(sq, block_q), _pick_block(sk, block_k)
    nq, nk = sq // bq, sk // bk
    scale = 1.0 / jnp.sqrt(jnp.array(dhq, jnp.float32))

    kb = k.reshape(b, nk, bk, kvh, -1).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nk, bk, kvh, dhv).transpose(1, 0, 2, 3, 4)

    def one_q_block(qi):
        qblk = jax.lax.dynamic_slice_in_dim(q, qi * bq, bq, axis=1)
        qf = qblk * jnp.asarray(scale, q.dtype)
        q_ids = q_offset + qi * bq + jnp.arange(bq)

        def kv_step(carry, xs):
            m, l, acc = carry
            kblk, vblk, ki = xs
            k_ids = ki * bk + jnp.arange(bk)
            # scores: (B, KV, G, Bq, Bk).  f32 accumulation via
            # preferred_element_type (casting inputs would materialise f32
            # copies of K/V and double the HBM traffic).
            s = jnp.einsum("bqkgd,bpkd->bkgqp", qf, kblk,
                           preferred_element_type=jnp.float32)
            # masking as a SMALL additive bias (bq, bk): a boolean mask
            # select gets hoisted by XLA into a precomputed
            # (nq, nk, B, KV, G, bq, bk) buffer — gigabytes of loop state.
            if causal or window is not None:
                ok = jnp.ones((bq, bk), bool)
                if causal:
                    ok &= k_ids[None, :] <= q_ids[:, None]
                if window is not None:
                    ok &= k_ids[None, :] > q_ids[:, None] - window
                s = s + jnp.where(ok, 0.0, NEG_INF)[None, None, None]
            if valid_len is not None:
                vbias = jnp.where(k_ids[None] < valid_len[:, None],
                                  0.0, NEG_INF)                # (B, Bk)
                s = s + vbias[:, None, None, None]
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            pv = jnp.einsum("bkgqp,bpkd->bkgqd",
                            p.astype(v.dtype), vblk,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kvh, g, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, bq), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, bq, dhv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (kb, vb, jnp.arange(nk)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 3, 1, 2, 4)                   # (B,Bq,KV,G,dhv)

    out = jax.lax.map(one_q_block, jnp.arange(nq))            # (nq,B,Bq,...)
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, kvh, g, dhv)
    return out.astype(v.dtype)


# ---------------------------------------------------------------------------
# Flash attention with custom VJP (recompute-based backward)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _flash_fn(causal: bool, window: Optional[int], q_offset: int,
              block_q: int, block_k: int):
    """Build (and cache) the custom-vjp flash attention for one mask config."""

    def fwd_pass(q, k, v):
        b, sq, kvh, g, dhq = q.shape
        sk, dhv = k.shape[1], v.shape[-1]
        bq, bk = _pick_block(sq, block_q), _pick_block(sk, block_k)
        nq, nk = sq // bq, sk // bk
        scale = jnp.asarray(1.0 / (dhq ** 0.5), q.dtype)
        # Block access pattern (hard-won on the dry-run memory reports):
        #   * inner loops scan over PRE-STACKED bf16 copies of K/V (a
        #     dynamic_slice with a traced index on a seq-SHARDED tensor
        #     triggers GSPMD "involuntary full rematerialization");
        #   * outer loops are STATIC python loops (lax.map would stack the
        #     per-block f32 outputs into a whole-tensor temp).
        kb = k.reshape(b, nk, bk, kvh, dhq).transpose(1, 0, 2, 3, 4)
        vb = v.reshape(b, nk, bk, kvh, dhv).transpose(1, 0, 2, 3, 4)

        def one_q_block(qi):
            qblk = jax.lax.dynamic_slice_in_dim(q, qi * bq, bq, 1) * scale
            q_ids = q_offset + qi * bq + jnp.arange(bq)

            def kv_step(carry, xs):
                m, l, acc = carry
                kblk, vblk, ki = xs
                k_ids = ki * bk + jnp.arange(bk)
                s = jnp.einsum("bqkgd,bpkd->bkgqp", qblk, kblk,
                               preferred_element_type=jnp.float32)
                bias = _block_bias(q_ids, k_ids, causal, window)
                if bias is not None:
                    s = s + bias[None, None, None]
                m_new = jnp.maximum(m, s.max(-1))
                p = jnp.exp(s - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l_new = l * corr + p.sum(-1)
                pv = jnp.einsum("bkgqp,bpkd->bkgqd", p.astype(v.dtype),
                                vblk, preferred_element_type=jnp.float32)
                return (m_new, l_new, acc * corr[..., None] + pv), None

            m0 = jnp.full((b, kvh, g, bq), NEG_INF, jnp.float32)
            l0 = jnp.zeros((b, kvh, g, bq), jnp.float32)
            a0 = jnp.zeros((b, kvh, g, bq, dhv), jnp.float32)
            (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                          (kb, vb, jnp.arange(nk)))
            out = acc / jnp.maximum(l, 1e-30)[..., None]
            lse = m + jnp.log(jnp.maximum(l, 1e-30))           # (B,KV,G,bq)
            return out.transpose(0, 3, 1, 2, 4).astype(v.dtype), lse

        out, lse = jax.lax.map(one_q_block, jnp.arange(nq))
        out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, kvh, g, dhv)
        lse = lse.transpose(1, 2, 3, 0, 4).reshape(b, kvh, g, sq)
        return out, lse

    def f(q, k, v):
        return fwd_pass(q, k, v)[0]

    def f_fwd(q, k, v):
        out, lse = fwd_pass(q, k, v)
        return out, (q, k, v, out, lse)

    def f_bwd(res, d_out):
        q, k, v, out, lse = res
        b, sq, kvh, g, dhq = q.shape
        sk, dhv = k.shape[1], v.shape[-1]
        bq, bk = _pick_block(sq, block_q), _pick_block(sk, block_k)
        nq, nk = sq // bq, sk // bk
        scale = jnp.asarray(1.0 / (dhq ** 0.5), q.dtype)
        qs = q * scale
        # cotangent arrives f32 from upstream norm math; carry it at the
        # model dtype (delta keeps f32 accuracy via preferred_element_type)
        delta = jnp.einsum("bqkgd,bqkgd->bkgq", d_out, out,
                           preferred_element_type=jnp.float32)
        d_out = d_out.astype(v.dtype)

        def sl(t, i, blk):
            return jax.lax.dynamic_slice_in_dim(t, i * blk, blk, 1)

        def sl_stat(t, qi):   # (B,KV,G,Sq) -> (B,KV,G,bq)
            return jax.lax.dynamic_slice_in_dim(t, qi * bq, bq, 3)

        kb = k.reshape(b, nk, bk, kvh, dhq).transpose(1, 0, 2, 3, 4)
        vb = v.reshape(b, nk, bk, kvh, dhv).transpose(1, 0, 2, 3, 4)
        qsb = qs.reshape(b, nq, bq, kvh, g, dhq).transpose(1, 0, 2, 3, 4, 5)
        dob = d_out.reshape(b, nq, bq, kvh, g, dhv).transpose(
            1, 0, 2, 3, 4, 5)

        def p_block(qi, ki, qblk, kblk, lse_q):
            q_ids = q_offset + qi * bq + jnp.arange(bq)
            k_ids = ki * bk + jnp.arange(bk)
            s = jnp.einsum("bqkgd,bpkd->bkgqp", qblk, kblk,
                           preferred_element_type=jnp.float32)
            bias = _block_bias(q_ids, k_ids, causal, window)
            if bias is not None:
                s = s + bias[None, None, None]
            return jnp.exp(s - lse_q[..., None])               # (B,KV,G,bq,bk)

        # ---- dQ: static python loop over q blocks, scan over kv blocks
        def dq_block(qi):
            qblk = jax.lax.dynamic_slice_in_dim(qsb, qi, 1, 0)[0]
            do_q = jax.lax.dynamic_slice_in_dim(dob, qi, 1, 0)[0]
            lse_q, delta_q = sl_stat(lse, qi), sl_stat(delta, qi)

            def step(acc, xs):
                kblk, vblk, ki = xs
                p = p_block(qi, ki, qblk, kblk, lse_q)
                dp = jnp.einsum("bqkgd,bpkd->bkgqp", do_q, vblk,
                                preferred_element_type=jnp.float32)
                ds = p * (dp - delta_q[..., None])
                dq = jnp.einsum("bkgqp,bpkd->bqkgd", ds.astype(k.dtype),
                                kblk, preferred_element_type=jnp.float32)
                return acc + dq, None

            acc0 = jnp.zeros((b, bq, kvh, g, dhq), jnp.float32)
            acc, _ = jax.lax.scan(step, acc0, (kb, vb, jnp.arange(nk)))
            return (acc * jnp.float32(scale)).astype(q.dtype)

        dq = jax.lax.map(dq_block, jnp.arange(nq))
        dq = dq.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, kvh, g, dhq)

        # ---- dK, dV: static python loop over kv blocks, scan over q blocks
        def dkv_block(ki):
            kblk = jax.lax.dynamic_slice_in_dim(kb, ki, 1, 0)[0]
            vblk = jax.lax.dynamic_slice_in_dim(vb, ki, 1, 0)[0]

            def step(carry, xs):
                dk_acc, dv_acc = carry
                qblk, doblk, qi = xs
                lse_q = jax.lax.dynamic_slice_in_dim(lse, qi * bq, bq, 3)
                delta_q = jax.lax.dynamic_slice_in_dim(delta, qi * bq, bq, 3)
                p = p_block(qi, ki, qblk, kblk, lse_q)
                dv = jnp.einsum("bkgqp,bqkgd->bpkd", p.astype(q.dtype),
                                doblk, preferred_element_type=jnp.float32)
                dp = jnp.einsum("bqkgd,bpkd->bkgqp", doblk, vblk,
                                preferred_element_type=jnp.float32)
                ds = p * (dp - delta_q[..., None])
                dk = jnp.einsum("bkgqp,bqkgd->bpkd", ds.astype(q.dtype),
                                qblk, preferred_element_type=jnp.float32)
                return (dk_acc + dk, dv_acc + dv), None

            z_k = jnp.zeros((b, bk, kvh, dhq), jnp.float32)
            z_v = jnp.zeros((b, bk, kvh, dhv), jnp.float32)
            (dk, dv), _ = jax.lax.scan(
                step, (z_k, z_v), (qsb, dob, jnp.arange(nq)))
            return dk.astype(k.dtype), dv.astype(v.dtype)

        dk, dv = jax.lax.map(dkv_block, jnp.arange(nk))
        dk = dk.transpose(1, 0, 2, 3, 4).reshape(b, sk, kvh, dhq)
        dv = dv.transpose(1, 0, 2, 3, 4).reshape(b, sk, kvh, dhv)
        return dq, dk, dv

    flash = jax.custom_vjp(f)
    flash.defvjp(f_fwd, f_bwd)
    return flash


# ===========================================================================
# Flash-decoding: one query token against a (possibly seq-sharded) cache
# ===========================================================================
def _decode_partial(q, k, v, valid):
    """Local attention partials.  q: (B,KV,G,dhq) k: (B,S,KV,dhk)
    v: (B,S,KV,dhv) valid: (B,S) -> (o, m, l) unnormalised."""
    dhq = q.shape[-1]
    scale = jnp.asarray(1.0 / (dhq ** 0.5), q.dtype)
    s = jnp.einsum("bkgd,bskd->bkgs", q * scale, k,
                   preferred_element_type=jnp.float32)
    s = s + jnp.where(valid, 0.0, NEG_INF)[:, None, None, :]
    m = s.max(-1)                                             # (B,KV,G)
    p = jnp.exp(s - m[..., None])
    l = p.sum(-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o, m, l


def _combine_partials(o, m, l):
    """Combine per-shard partials stacked on axis 0."""
    m_star = m.max(0)
    w = jnp.exp(m - m_star[None])
    l_star = (l * w).sum(0)
    o_star = (o * w[..., None]).sum(0)
    return o_star / jnp.maximum(l_star, 1e-30)[..., None]


def _pallas_decode(q, k_cache, v_cache, valid):
    """Route single-device decode through the Pallas flash-decoding kernel
    (kernels/flash_decode.py; interpret mode on the CPU backend only, via
    kernels.ops).

    The kernel works in a flat (BH, ...) layout with one KV row per query
    head, so the grouped cache is broadcast across the G query heads — the
    G-fold read amplification is the price of the kernel's HBM->VMEM
    streaming pipeline and only applies on this explicitly-requested path.
    Requires dhk == dhv: ``core.modules.resolve_attn_impl`` never selects
    Pallas for MLA's asymmetric latent head.

    Batched ragged decode rides through unchanged: the (B, S) ``valid``
    mask is per ROW, so a stacked batch of requests at different cache
    positions is one kernel call over BH query rows — exactly how the
    continuous-batching scheduler amortises the cache stream.
    """
    from repro.kernels import ops

    b, kv, g, dh = q.shape
    s, dv = k_cache.shape[1], v_cache.shape[-1]
    if dv != dh:
        raise ValueError(f"Pallas decode needs equal K/V head widths, got "
                         f"{dh} and {dv}")
    bh = b * kv * g
    qf = q.reshape(bh, dh)
    kf = jnp.broadcast_to(k_cache.transpose(0, 2, 1, 3)[:, :, None],
                          (b, kv, g, s, dh)).reshape(bh, s, dh)
    vf = jnp.broadcast_to(v_cache.transpose(0, 2, 1, 3)[:, :, None],
                          (b, kv, g, s, dv)).reshape(bh, s, dv)
    validf = jnp.broadcast_to(valid[:, None, None], (b, kv, g, s)
                              ).reshape(bh, s)
    # the chip needs a lane-aligned (multiple of 128) or whole-cache block
    bk = next((c for c in (512, 256, 128) if s % c == 0), s)
    out = ops.decode(qf, kf, vf, validf, block_k=bk)
    return out.reshape(b, kv, g, dv)


def flash_decode(q, k_cache, v_cache, valid, ctx: Optional[ShardingCtx],
                 impl: Optional[str] = None):
    """q: (B,KV,G,dhq); caches: (B,S,KV,dh*); valid: (B,S) -> (B,KV,G,dhv).

    With ``ctx``: cache sequence dim sharded over the model axis; partials
    combined with an all-gather of (o, m, l) (tiny: no seq dim).

    ``impl="pallas"`` (single-device only) runs the Pallas flash-decoding
    kernel instead of the jnp online softmax — the engine's KV decode path
    selects it so the cache streams HBM -> VMEM in blocks.
    """
    if ctx is None:
        if impl == "pallas":
            return _pallas_decode(q, k_cache, v_cache, valid)
        o, m, l = _decode_partial(q, k_cache, v_cache, valid)
        return _combine_partials(o[None], m[None], l[None]).astype(v_cache.dtype)

    bs, ax = ctx.batch_spec, ctx.model_axis

    def local(qq, kk, vv, va):
        o, m, l = _decode_partial(qq, kk, vv, va)
        # psum-based softmax combine: pmax the running max, then psum the
        # rescaled (l, o) partials — cheaper than all-gathering partials and
        # provably model-axis-invariant (keeps shard_map's VMA check happy).
        m_star = jax.lax.pmax(m, ax)
        w = jnp.exp(m - m_star)
        l_star = jax.lax.psum(l * w, ax)
        o_star = jax.lax.psum(o * w[..., None], ax)
        return (o_star / jnp.maximum(l_star, 1e-30)[..., None]).astype(
            vv.dtype)

    return shard_map(
        local, mesh=ctx.mesh,
        in_specs=(P(bs, None, None, None), P(bs, ax, None, None),
                  P(bs, ax, None, None), P(bs, ax)),
        out_specs=P(bs, None, None, None))(q, k_cache, v_cache, valid)


def _pallas_paged_decode(q, k_pool, v_pool, tables, pos):
    """Route paged decode through the Pallas block-table kernel
    (kernels/paged_decode.py).  The kernel consumes the scheduler's
    native (P, page, KV, dh) pool layout directly — its BlockSpec
    index_map dereferences the scalar-prefetched table per (row, block)
    and slices the kv head, so no transpose/densify of the pool is ever
    materialised.  The grouped tile is re-read per q-head group (the
    same G-fold read amplification as ``_pallas_decode``, the price of
    the HBM -> VMEM streaming pipeline).
    """
    from repro.kernels import ops

    return ops.paged_decode(q, k_pool, v_pool, tables,
                            (pos + 1).astype(jnp.int32))


def _pallas_paged_verify(q, k_pool, v_pool, tables, lengths):
    """Stacked W-query sibling of ``_pallas_paged_decode``: one kernel
    call scores a whole speculation window, each query applying its own
    causal frontier inside the block-table gather."""
    from repro.kernels import ops

    return ops.paged_verify(q, k_pool, v_pool, tables,
                            lengths.astype(jnp.int32))


def gqa_decode_paged(params, x, cfg: ModelConfig, pools, tables, pos, *,
                     attn_impl=None):
    """GQA decode against the PAGED cache: pools{k,v}: (P, page, KV, dh);
    tables: (B, NB) block tables; pos: (B,) ragged positions.

    The new token's K/V is written straight into its page
    (``tables[b, pos // page]``, slot ``pos % page`` — the scheduler
    guarantees that page is private, copy-on-writing shared pages at
    the round boundary).  ``attn_impl="pallas"`` runs the block-table
    kernel; the jnp path gathers the row's pages into the logically
    contiguous cache, which is bit-identical to a dense decode over the
    same padded length.  Full causal attention only (the paged serving
    path does not model sliding windows).
    """
    b = x.shape[0]
    kv, g, dh = cfg.n_kv_heads, cfg.q_heads_per_kv, cfg.head_dim
    page = pools["k"].shape[1]
    nb = tables.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    pos_b = (pos.reshape(b, 1) if pos.ndim
             else jnp.full((b, 1), pos, jnp.int32))
    posv = pos_b[:, 0]
    q, k, v = _project_qkv(params, x, cfg)
    q = common.apply_rope(q, pos_b, cfg.rope_theta)
    k = common.apply_rope(k, pos_b, cfg.rope_theta)

    rows = jnp.arange(b)
    pids = tables[rows, posv // page]
    k_pool = pools["k"].at[pids, posv % page].set(
        k[:, 0].astype(pools["k"].dtype))
    v_pool = pools["v"].at[pids, posv % page].set(
        v[:, 0].astype(pools["v"].dtype))

    qh = q.reshape(b, kv, g, dh)
    if attn_impl == "pallas":
        out = _pallas_paged_decode(qh, k_pool, v_pool, tables, posv)
    else:
        k_cache = k_pool[tables].reshape(b, nb * page, kv, dh)
        v_cache = v_pool[tables].reshape(b, nb * page, kv, dh)
        valid = jnp.arange(nb * page)[None, :] <= pos_b
        out = flash_decode(qh, k_cache, v_cache,
                           jnp.broadcast_to(valid, (b, nb * page)), None)
    out = out.reshape(b, 1, kv * g * dh) @ params["w_o"]
    return out, {"k": k_pool, "v": v_pool}


def gqa_verify_paged(params, x, cfg: ModelConfig, pools, tables, pos, *,
                     attn_impl=None):
    """Stacked multi-token GQA decode against the PAGED cache — the
    speculative-verify sibling of ``gqa_decode_paged``.

    ``x``: (B, W, D) — W consecutive tokens per row (the last committed
    token followed by the draft's proposals); ``pos``: (B,) cache slot
    of the FIRST stacked token.  All W tokens' K/V are written into
    their pages up front (the scheduler guarantees the write-range pages
    are private), then each query attends causally up to its own slot —
    token i sees slots ``<= pos + i`` — so row i's output equals what W
    sequential ``gqa_decode_paged`` calls would produce, in ONE pass
    over the pool.  Rejected suffixes leave garbage K/V past the
    accepted length; it is masked by every later valid-length mask and
    overwritten before it ever unmasks.

    ``attn_impl="pallas"`` runs the stacked block-table kernel; the jnp
    path flattens (B, W) into the batch dim and reuses the EXACT decode
    attention (``flash_decode``) so verify logits are bit-identical to
    the sequential jnp decode path.
    """
    b, w, _ = x.shape
    kv, g, dh = cfg.n_kv_heads, cfg.q_heads_per_kv, cfg.head_dim
    page = pools["k"].shape[1]
    nb = tables.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    pos_b = (pos.reshape(b, 1) if pos.ndim
             else jnp.full((b, 1), pos, jnp.int32))
    positions = pos_b + jnp.arange(w, dtype=jnp.int32)[None, :]  # (B, W)
    q, k, v = _project_qkv(params, x, cfg)
    q = common.apply_rope(q, positions, cfg.rope_theta)
    k = common.apply_rope(k, positions, cfg.rope_theta)

    pids = tables[jnp.arange(b)[:, None], positions // page]     # (B, W)
    slots = positions % page
    k_pool = pools["k"].at[pids, slots].set(k.astype(pools["k"].dtype))
    v_pool = pools["v"].at[pids, slots].set(v.astype(pools["v"].dtype))

    qh = q.reshape(b, w, kv, g, dh)
    if attn_impl == "pallas":
        out = _pallas_paged_verify(qh, k_pool, v_pool, tables,
                                   pos_b[:, 0] + w)
    else:
        s_tot = nb * page
        k_cache = k_pool[tables].reshape(b, s_tot, kv, dh)
        v_cache = v_pool[tables].reshape(b, s_tot, kv, dh)
        valid = (jnp.arange(s_tot)[None, None, :]
                 <= positions[:, :, None])                    # (B, W, S)
        qf = qh.reshape(b * w, kv, g, dh)
        kf = jnp.broadcast_to(k_cache[:, None],
                              (b, w, s_tot, kv, dh)
                              ).reshape(b * w, s_tot, kv, dh)
        vf = jnp.broadcast_to(v_cache[:, None],
                              (b, w, s_tot, kv, dh)
                              ).reshape(b * w, s_tot, kv, dh)
        out = flash_decode(qf, kf, vf, valid.reshape(b * w, s_tot), None)
        out = out.reshape(b, w, kv, g, dh)
    out = out.reshape(b, w, kv * g * dh) @ params["w_o"]
    return out, {"k": k_pool, "v": v_pool}


def cache_update(cache, new, pos, ctx: Optional[ShardingCtx]):
    """Write ``new`` (B, KV, dh) into ``cache`` (B, S, KV, dh) at index ``pos``.

    ``pos`` may be a scalar (one write slot for the whole batch — the
    single-request decode path) or a (B,) vector of RAGGED per-row slots:
    the continuous-batching scheduler stacks requests whose sequences are
    at different lengths, so each row writes its own cache slot.

    Sequence dim may be sharded over the model axis: each shard applies a
    masked write iff ``pos`` lands in its range (no cross-shard traffic).
    """
    if ctx is None:
        pos = jnp.asarray(pos)
        if pos.ndim == 0:
            return jax.lax.dynamic_update_slice_in_dim(
                cache, new[:, None].astype(cache.dtype), pos, axis=1)
        row_write = jax.vmap(
            lambda c, n, p: jax.lax.dynamic_update_slice_in_dim(
                c, n[None], p, axis=0))
        return row_write(cache, new.astype(cache.dtype), pos)
    if jnp.ndim(pos):
        raise NotImplementedError(
            "ragged per-row cache positions are single-device only "
            "(the seq-sharded serving cache keeps one slot per step)")

    bs, ax = ctx.batch_spec, ctx.model_axis

    def local(c, n):
        s_loc = c.shape[1]
        start = jax.lax.axis_index(ax) * s_loc
        idx = pos - start
        in_range = (idx >= 0) & (idx < s_loc)
        idx = jnp.clip(idx, 0, s_loc - 1)
        # out-of-range shards overwrite the slot with its EXISTING row —
        # a row-level select instead of where(in_range, updated, c), which
        # materialises a full second copy of the cache per layer step
        old_row = jax.lax.dynamic_slice_in_dim(c, idx, 1, axis=1)
        val = jnp.where(in_range, n[:, None].astype(c.dtype), old_row)
        return jax.lax.dynamic_update_slice_in_dim(c, val, idx, axis=1)

    return shard_map(
        local, mesh=ctx.mesh,
        in_specs=(P(bs, ax, None, None), P(bs, None, None)),
        out_specs=P(bs, ax, None, None))(cache, new)


# ===========================================================================
# GQA block: prefill + decode
# ===========================================================================
def _project_qkv(params, x, cfg: ModelConfig):
    b, s, _ = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ params["w_q"]
    k = x @ params["w_k"]
    v = x @ params["w_v"]
    if cfg.qkv_bias:
        q, k, v = q + params["b_q"], k + params["b_k"], v + params["b_v"]
    return (q.reshape(b, s, h, dh), k.reshape(b, s, kv, dh),
            v.reshape(b, s, kv, dh))


def gqa_prefill(params, x, cfg: ModelConfig, ctx, positions, *,
                causal=True, make_cache=True):
    """x: (B,S,D) -> (out (B,S,D), cache | None)."""
    b, s, _ = x.shape
    kv, g, dh = cfg.n_kv_heads, cfg.q_heads_per_kv, cfg.head_dim
    q, k, v = _project_qkv(params, x, cfg)
    q = common.apply_rope(q, positions, cfg.rope_theta)
    k = common.apply_rope(k, positions, cfg.rope_theta)
    qg = q.reshape(b, s, kv, g, dh)
    out = chunked_attention(qg, k, v, causal=causal,
                            window=cfg.sliding_window)
    out = out.reshape(b, s, kv * g * dh) @ params["w_o"]
    cache = None
    if make_cache:
        if ctx is not None:  # live seq-sharded for the decode phase
            k = constrain(ctx, k, ctx.batch_spec, ctx.model_axis)
            v = constrain(ctx, v, ctx.batch_spec, ctx.model_axis)
        cache = {"k": k, "v": v}
    return out, cache


def gqa_mrope_prefill(params, x, cfg: ModelConfig, ctx, positions3, *,
                      make_cache=True):
    """Qwen2-VL style prefill with 3-section M-RoPE positions (3,B,S)."""
    b, s, _ = x.shape
    kv, g, dh = cfg.n_kv_heads, cfg.q_heads_per_kv, cfg.head_dim
    q, k, v = _project_qkv(params, x, cfg)
    q = common.apply_mrope(q, positions3, cfg.mrope_sections, cfg.rope_theta)
    k = common.apply_mrope(k, positions3, cfg.mrope_sections, cfg.rope_theta)
    qg = q.reshape(b, s, kv, g, dh)
    out = chunked_attention(qg, k, v, causal=True, window=cfg.sliding_window)
    out = out.reshape(b, s, kv * g * dh) @ params["w_o"]
    cache = None
    if make_cache:
        if ctx is not None:
            k = constrain(ctx, k, ctx.batch_spec, ctx.model_axis)
            v = constrain(ctx, v, ctx.batch_spec, ctx.model_axis)
        cache = {"k": k, "v": v}
    return out, cache


def gqa_decode(params, x, cfg: ModelConfig, ctx, cache, pos, *,
               mrope_positions3=None, attn_impl=None):
    """x: (B,1,D); cache{k,v}: (B,S,KV,dh); pos: scalar or RAGGED (B,)
    vector of per-row cache positions -> (out, cache)."""
    b = x.shape[0]
    kv, g, dh = cfg.n_kv_heads, cfg.q_heads_per_kv, cfg.head_dim
    pos = jnp.asarray(pos, jnp.int32)
    pos_b = pos.reshape(b, 1) if pos.ndim else jnp.full((b, 1), pos,
                                                        jnp.int32)
    q, k, v = _project_qkv(params, x, cfg)
    if mrope_positions3 is not None:
        q = common.apply_mrope(q, mrope_positions3, cfg.mrope_sections,
                               cfg.rope_theta)
        k = common.apply_mrope(k, mrope_positions3, cfg.mrope_sections,
                               cfg.rope_theta)
    else:
        q = common.apply_rope(q, pos_b, cfg.rope_theta)
        k = common.apply_rope(k, pos_b, cfg.rope_theta)
    s_cache = cache["k"].shape[1]
    write_idx = pos % s_cache                       # ring buffer for windows
    k_cache = cache_update(cache["k"], k[:, 0], write_idx, ctx)
    v_cache = cache_update(cache["v"], v[:, 0], write_idx, ctx)
    idx = jnp.arange(s_cache)
    if cfg.sliding_window is not None and cfg.sliding_window < s_cache:
        # full-length cache, windowed mask (writes are positional)
        valid = ((idx[None, :] <= pos_b)
                 & (idx[None, :] > pos_b - cfg.sliding_window))
    elif cfg.sliding_window is not None:
        # ring cache at window size: every written slot is a valid key
        # (keys carry absolute rope; softmax is permutation-invariant)
        valid = idx[None, :] < jnp.minimum(pos_b + 1, s_cache)
    else:
        valid = idx[None, :] <= pos_b
    valid = jnp.broadcast_to(valid, (b, s_cache))
    qh = q.reshape(b, kv, g, dh)
    out = flash_decode(qh, k_cache, v_cache, valid, ctx, impl=attn_impl)
    out = out.reshape(b, 1, kv * g * dh) @ params["w_o"]
    return out, {"k": k_cache, "v": v_cache}


def gqa_empty_cache(cfg: ModelConfig, batch: int, seq: int, dtype=None):
    dh, kv = cfg.head_dim, cfg.n_kv_heads
    dt = dtype or cfg.jnp_dtype
    return {"k": jnp.zeros((batch, seq, kv, dh), dt),
            "v": jnp.zeros((batch, seq, kv, dh), dt)}


# ===========================================================================
# Cross attention (encoder-decoder)
# ===========================================================================
def cross_attn_prefill_kv(params, enc_out, cfg: ModelConfig, ctx):
    """Compute the static cross-attention KV cache from encoder output."""
    b, s, _ = enc_out.shape
    kv, dh = cfg.n_kv_heads, cfg.head_dim
    k = (enc_out @ params["w_k"]).reshape(b, s, kv, dh)
    v = (enc_out @ params["w_v"]).reshape(b, s, kv, dh)
    if cfg.qkv_bias:
        k, v = k + params["b_k"].reshape(kv, dh), v + params["b_v"].reshape(kv, dh)
    if ctx is not None:
        k = constrain(ctx, k, ctx.batch_spec, ctx.model_axis)
        v = constrain(ctx, v, ctx.batch_spec, ctx.model_axis)
    return {"k": k, "v": v}


def cross_attn_apply(params, x, kv_cache, cfg: ModelConfig, ctx):
    """x: (B,Sq,D) attends (non-causal) over encoder KV."""
    b, sq, _ = x.shape
    kv, g, dh = cfg.n_kv_heads, cfg.q_heads_per_kv, cfg.head_dim
    q = (x @ params["w_q"]).reshape(b, sq, cfg.n_heads, dh)
    if cfg.qkv_bias:
        q = q + params["b_q"].reshape(cfg.n_heads, dh)
    k, v = kv_cache["k"], kv_cache["v"]
    if sq == 1:
        valid = jnp.ones((b, k.shape[1]), bool)
        out = flash_decode(q.reshape(b, kv, g, dh), k, v, valid, ctx)
        out = out.reshape(b, 1, kv * g * dh)
    else:
        qg = q.reshape(b, sq, kv, g, dh)
        out = chunked_attention(qg, k, v, causal=False)
        out = out.reshape(b, sq, kv * g * dh)
    return out @ params["w_o"]


# ===========================================================================
# MLA (Multi-head Latent Attention) — MiniCPM3 / DeepSeek style
# ===========================================================================
def mla_prefill(params, x, cfg: ModelConfig, ctx, positions, *,
                make_cache=True):
    b, s, _ = x.shape
    h = cfg.n_heads
    dn, dr, dv = cfg.v_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    dc = cfg.kv_lora_rank

    qc = common.rms_norm(x @ params["w_dq"], params["q_norm"], cfg.norm_eps)
    q_nope = (qc @ params["w_uq_nope"]).reshape(b, s, h, dn)
    q_rope = (qc @ params["w_uq_rope"]).reshape(b, s, h, dr)
    q_rope = common.apply_rope(q_rope, positions, cfg.rope_theta)

    c = common.rms_norm(x @ params["w_dkv"], params["kv_norm"], cfg.norm_eps)
    k_rope = common.apply_rope((x @ params["w_kr"]).reshape(b, s, 1, dr),
                               positions, cfg.rope_theta)
    k_nope = (c @ params["w_uk"]).reshape(b, s, h, dn)
    v = (c @ params["w_uv"]).reshape(b, s, h, dv)

    # Assemble per-head q/k of width (dn + dr); kv_heads == n_heads here.
    q_full = jnp.concatenate([q_nope, q_rope], -1)             # (B,S,H,dn+dr)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, s, h, dr))], -1)
    qg = q_full.reshape(b, s, h, 1, dn + dr)
    out = chunked_attention(qg, k_full, v, causal=True)
    out = out.reshape(b, s, h * dv) @ params["w_o"]
    cache = None
    if make_cache:
        if ctx is not None:
            c = constrain(ctx, c, ctx.batch_spec, ctx.model_axis)
            k_rope = constrain(ctx, k_rope, ctx.batch_spec, ctx.model_axis)
        cache = {"c": c, "kr": k_rope[:, :, 0]}
    return out, cache


def mla_decode(params, x, cfg: ModelConfig, ctx, cache, pos):
    """Absorbed-matrix MLA decode over the latent cache.

    The latent cache is treated as a single virtual KV head of width
    (kv_lora_rank + rope_head_dim); W_uk is absorbed into the query and
    W_uv into the output projection, so decode never expands per-head K/V.
    """
    b = x.shape[0]
    h, dn, dr = cfg.n_heads, cfg.v_head_dim, cfg.rope_head_dim
    dc, dv = cfg.kv_lora_rank, cfg.v_head_dim

    qc = common.rms_norm(x @ params["w_dq"], params["q_norm"], cfg.norm_eps)
    q_nope = (qc @ params["w_uq_nope"]).reshape(b, 1, h, dn)
    q_rope = (qc @ params["w_uq_rope"]).reshape(b, 1, h, dr)
    pos = jnp.asarray(pos, jnp.int32)
    pos_b = pos.reshape(b, 1) if pos.ndim else jnp.full((b, 1), pos,
                                                        jnp.int32)
    q_rope = common.apply_rope(q_rope, pos_b, cfg.rope_theta)

    # Absorb W_uk: q_abs[h] = q_nope[h] @ W_uk[h].T  -> latent space (dc)
    w_uk = params["w_uk"].reshape(dc, h, dn)
    q_abs = jnp.einsum("bhn,chn->bhc", q_nope[:, 0], w_uk)     # (B,H,dc)
    q_eff = jnp.concatenate([q_abs, q_rope[:, 0]], -1)         # (B,H,dc+dr)

    c_new = common.rms_norm(x @ params["w_dkv"], params["kv_norm"],
                            cfg.norm_eps)[:, 0]                # (B,dc)
    kr_new = common.apply_rope(
        (x @ params["w_kr"]).reshape(b, 1, 1, dr), pos_b,
        cfg.rope_theta)[:, 0, 0]                               # (B,dr)

    s_cache = cache["c"].shape[1]
    kv_eff_new = jnp.concatenate([c_new, kr_new], -1)          # (B,dc+dr)
    # store latent + rope jointly: cache c:(B,S,dc), kr:(B,S,dr)
    c_cache = _cache_update_2d(cache["c"], c_new, pos, ctx)
    kr_cache = _cache_update_2d(cache["kr"], kr_new, pos, ctx)

    k_eff = jnp.concatenate([c_cache, kr_cache], -1)[:, :, None]  # (B,S,1,·)
    v_eff = c_cache[:, :, None]                                   # (B,S,1,dc)
    idx = jnp.arange(s_cache)
    valid = jnp.broadcast_to(idx[None] <= pos_b, (b, s_cache))
    o_lat = flash_decode(q_eff[:, None], k_eff, v_eff, valid, ctx)  # (B,1,H,dc)
    # Un-absorb W_uv: out[h] = o_lat[h] @ W_uv[h]
    w_uv = params["w_uv"].reshape(dc, h, dv)
    out = jnp.einsum("bhc,chv->bhv", o_lat[:, 0], w_uv).reshape(b, 1, h * dv)
    return out @ params["w_o"], {"c": c_cache, "kr": kr_cache}


def _cache_update_2d(cache, new, pos, ctx: Optional[ShardingCtx]):
    """cache: (B,S,F); new: (B,F)."""
    c4 = cache[:, :, None, :]
    out = cache_update(c4, new[:, None, :], pos, ctx)
    return out[:, :, 0, :]


def mla_empty_cache(cfg: ModelConfig, batch: int, seq: int, dtype=None):
    dt = dtype or cfg.jnp_dtype
    return {"c": jnp.zeros((batch, seq, cfg.kv_lora_rank), dt),
            "kr": jnp.zeros((batch, seq, cfg.rope_head_dim), dt)}
