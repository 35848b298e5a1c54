"""Plain float32 reference of the dense decoder the program serves.

The model as the program defines it (``family: dense``): token embedding,
then per layer a pre-RMSNorm grouped-query attention block with rotary
positions and a pre-RMSNorm MLP (GELU, tanh form, or SiLU-gated), each
added to the residual stream, then a final RMSNorm and an untied output
head over the padded vocabulary.  Written from that description in plain
``jax.numpy``: no kernel, no cache, no batching tricks, and nothing
imported from the program.

``init`` makes the weights from a seed in one jitted call, in the
parameter layout the program's checkpoint partitioner takes (stacked
layers).  The benchmark hands the same weights to the program and, once
its window has closed, makes them again here for the comparison.

``served_logits`` runs a whole prompt plus its served tokens through the
model (teacher forcing) and returns the logits that predicted each served
token.  Every matmul runs at ``Precision.HIGHEST``: on a TPU a float32
matmul otherwise runs in bfloat16 passes.  At a lower precision the same
function is the control.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def padded_vocab(cfg: dict) -> int:
    p = cfg["vocab_pad_to"]
    return (cfg["vocab_size"] + p - 1) // p * p


def _check(cfg: dict) -> None:
    want = {"family": "dense", "attention": "gqa", "qkv_bias": False,
            "tie_embeddings": False, "sliding_window": None}
    for key, val in want.items():
        if cfg.get(key, val) != val:
            raise ValueError(f"dense_decoder reference: {key}="
                             f"{cfg.get(key)!r}, only {val!r} is defined")


def shapes(cfg: dict) -> dict:
    """Parameter shapes, nested as the program's param tree."""
    _check(cfg)
    L, d, f = cfg["num_layers"], cfg["d_model"], cfg["d_ff"]
    hq = cfg["n_heads"] * cfg["head_dim"]
    hkv = cfg["n_kv_heads"] * cfg["head_dim"]
    v = padded_vocab(cfg)
    mlp = {"w_up": (L, d, f), "w_down": (L, f, d)}
    if cfg["gated_mlp"]:
        mlp["w_gate"] = (L, d, f)
    return {
        "embed": (v, d),
        "layers": {
            "attn_norm": (L, d),
            "attn": {"w_q": (L, d, hq), "w_k": (L, d, hkv),
                     "w_v": (L, d, hkv), "w_o": (L, hq, d)},
            "ffn_norm": (L, d),
            "mlp": mlp,
        },
        "final_norm": (d,),
        "lm_head": (d, v),
    }


def _leaf(key, path: str, shape, dtype):
    """Norm scales are ones; the embedding is N(0, 0.02); every other
    matrix is N(0, 1/fan_in), fan_in being its second-to-last axis."""
    if path.endswith("norm"):
        return jnp.ones(shape, dtype)
    scale = 0.02 if path == "embed" else 1.0 / math.sqrt(shape[-2])
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (more than 32 bits
    allowed: the high bits are folded in)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


@functools.lru_cache(maxsize=None)
def _init_fn(cfg_items: tuple, dtype: str):
    cfg = dict(cfg_items)
    tree = shapes(cfg)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 tree, is_leaf=lambda x: isinstance(x, tuple))[0]]
    treedef = jax.tree_util.tree_structure(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    leaves = jax.tree_util.tree_leaves(
        tree, is_leaf=lambda x: isinstance(x, tuple))

    @jax.jit
    def make(key):
        out = []
        for i, (path, shape) in enumerate(zip(paths, leaves)):
            name = path.split("'")[-2]
            out.append(_leaf(jax.random.fold_in(key, i), name, shape,
                             jnp.dtype(dtype)))
        return jax.tree_util.tree_unflatten(treedef, out)

    return make


def _hashable(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))
                        or v is None))


def init(cfg: dict, seed: int, dtype: str | None = None):
    """All weights from ``seed``, made on the default device in one
    jitted call, in ``dtype`` (the configuration's own by default)."""
    return _init_fn(_hashable(cfg), dtype or cfg["dtype"])(seed_key(seed))


# ---------------------------------------------------------------------------
# Forward pass
# ---------------------------------------------------------------------------
def _mm(a, b, precision):
    return jnp.matmul(a, b, precision=precision)


def _rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (out * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """x (B, S, heads, dh) at positions 0..S-1."""
    s, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv       # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


def _gelu_tanh(x):
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


def _layer(cfg, p, x, precision):
    b, s, _ = x.shape
    h_, kv, dh = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    g = h_ // kv
    h = _rms_norm(x, p["attn_norm"], cfg["norm_eps"])
    q = _rope(_mm(h, p["attn"]["w_q"], precision).reshape(b, s, h_, dh),
              cfg["rope_theta"])
    k = _rope(_mm(h, p["attn"]["w_k"], precision).reshape(b, s, kv, dh),
              cfg["rope_theta"])
    v = _mm(h, p["attn"]["w_v"], precision).reshape(b, s, kv, dh)
    q = q.reshape(b, s, kv, g, dh)       # query head i reads kv head i // g
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k, precision=precision,
                        preferred_element_type=jnp.float32) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    att = jnp.einsum("bkgqs,bskd->bqkgd", probs, v, precision=precision)
    x = x + _mm(att.reshape(b, s, h_ * dh), p["attn"]["w_o"], precision)
    h = _rms_norm(x, p["ffn_norm"], cfg["norm_eps"])
    m = p["mlp"]
    if "w_gate" in m:
        gate = _mm(h, m["w_gate"], precision)
        up = gate * jax.nn.sigmoid(gate) * _mm(h, m["w_up"], precision)
    else:
        up = _gelu_tanh(_mm(h, m["w_up"], precision))
    return x + _mm(up, m["w_down"], precision)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _served_logits(params, tokens, at, *, cfg_items, precision):
    cfg = dict(cfg_items)
    x = params["embed"][tokens]

    def body(x, p):
        return _layer(cfg, p, x, precision), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    h = _rms_norm(x, params["final_norm"], cfg["norm_eps"])
    h = jnp.take_along_axis(h, at[..., None], axis=1)       # (B, N, D)
    return _mm(h, params["lm_head"], precision).astype(jnp.float32)


PRECISIONS = {"highest": HIGHEST, "high": jax.lax.Precision.HIGH,
              "default": jax.lax.Precision.DEFAULT}


def served_logits(cfg: dict, params, tokens, at, *,
                  precision: str = "highest"):
    """Logits (B, N, V) at positions ``at`` (B, N) of ``tokens`` (B, T).

    Causal attention makes right-padding of ``tokens`` harmless, so
    callers pad every row to one length and compile once.  The
    reference is float32 weights at ``"highest"``; a control passes
    ``"high"`` (three bfloat16 passes) or bfloat16 weights at
    ``"default"``."""
    return _served_logits(params, tokens, at, cfg_items=_hashable(cfg),
                          precision=PRECISIONS[precision])
