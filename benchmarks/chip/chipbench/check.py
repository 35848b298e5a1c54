"""The comparison that decides ``correct``.

Every request the run served is compared, all of its served tokens: the
plain reference runs each prompt with the tokens the program served
after it (teacher forcing), and for every served token reads how far its
logit lies below the reference's best logit at that position.  A greedy
server that computes the model right serves the best token or one that
ties it to rounding, so the widest such gap is small; a wrong weight,
page, mask or head moves it by the logits' own scale.

The logits the program's head computed for every decode row of the
window are compared too, logit by logit, with the reference's
(``logit_err``).

The control puts the reference itself in the program's place at the next
lower precision: at the same positions of the same sequences, the gap of
the token the control ranks first, and its logits against the
reference's.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# The precision of every float32 matmul, the program's (the harness sets
# it as JAX's default before the program is built) and the reference's:
# the configurations state float32, which a TPU computes in full only at
# ``highest``.  The control runs one step below it.
PRECISION = "highest"

# Rows per reference call are capped so the (rows, heads, T, T) float32
# attention scores stay under this many bytes.
SCORES_BYTES = 1 << 30


def rows_per_block(cfg: dict, total_len: int, n_rows: int) -> int:
    per_row = cfg["n_heads"] * total_len * total_len * 4
    return max(1, min(n_rows, SCORES_BYTES // per_row))


def compare(ref, cfg: dict, seed: int, served: Sequence[Tuple],
            total_len: int, n_out: int, *,
            program_logits: Optional[Dict[int, list]] = None,
            control: Optional[str] = None) -> Dict:
    """The numbers ``correct`` compares, over every request served.

    ``served`` holds (request id, prompt, served tokens).  ``logit_gap``
    is the widest gap between the reference's best logit and its logit
    for the served token; ``logit_err`` the widest difference between a
    logit the program computed (``program_logits``: request id -> list of
    (index of the served token it predicted, logits row)) and the
    reference's.  With ``control`` (``"high"``: float32 weights at three
    bfloat16 passes; ``"bfloat16"``: bfloat16 weights and activations)
    the control stands in the program's place at every served position:
    its first choices are the served tokens and its logits the program's.
    Every row is padded to ``total_len`` tokens and ``n_out`` positions,
    so the reference compiles once per cell."""
    import jax
    import jax.numpy as jnp

    rows = [(rid, np.asarray(p, np.int32), list(map(int, s)))
            for rid, p, s in served if len(s)]
    out = {"logit_gap": None, "logit_err": None, "tokens_compared": 0,
           "logits_compared": 0, "greedy_match": None}
    if not rows:
        return out
    params = ref.init(cfg, seed)
    ctl = None
    if control == "bfloat16":
        ctl = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    elif control == "high":
        ctl = params
    block = rows_per_block(cfg, total_len, len(rows))
    gap, err, n_tok, n_log, n_match = -math.inf, -math.inf, 0, 0, 0
    for i in range(0, len(rows), block):
        part = rows[i:i + block]
        tokens = np.zeros((block, total_len), np.int32)
        at = np.zeros((block, n_out), np.int32)
        picked = np.zeros((block, n_out), np.int32)
        mask = np.zeros((block, n_out), bool)
        for j, (_, prompt, toks) in enumerate(part):
            seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
            seq = seq[:total_len]
            tokens[j, :len(seq)] = seq
            n = min(len(toks), n_out, total_len - len(prompt))
            # served token k was predicted at position len(prompt) - 1 + k
            at[j, :n] = np.arange(n) + len(prompt) - 1
            picked[j, :n] = toks[:n]
            mask[j, :n] = True
        t, a = jnp.asarray(tokens), jnp.asarray(at)
        logits = np.asarray(ref.served_logits(cfg, params, t, a,
                                              precision=PRECISION))
        best = logits.max(-1)
        if ctl is not None:
            c = np.asarray(ref.served_logits(
                cfg, ctl, t, a,
                precision="high" if control == "high" else "default"))
            picked = np.where(mask, c.argmax(-1), picked)
            d = np.where(mask, np.abs(c - logits).max(-1), -np.inf)
            err = max(err, float(d.max()))
            n_log += int(mask.sum())
        elif program_logits is not None:
            for j, (rid, _, _) in enumerate(part):
                for k, row in program_logits.get(rid, ()):
                    if k < n_out and mask[j, k]:
                        err = max(err, float(np.abs(row - logits[j, k])
                                             .max()))
                        n_log += 1
        got = np.take_along_axis(logits, picked[..., None], -1)[..., 0]
        gap = max(gap, float(np.where(mask, best - got, -np.inf).max()))
        n_tok += int(mask.sum())
        n_match += int((mask & (logits.argmax(-1) == picked)).sum())
    del params, ctl
    out.update(logit_gap=gap, tokens_compared=n_tok,
               greedy_match=n_match / n_tok)
    if n_log:
        out.update(logit_err=err, logits_compared=n_log)
    return out


def alter_one_token(served: Sequence[Tuple], cfg: dict) -> List[Tuple]:
    """``served`` with the middle served token of the longest request
    replaced by the next id: what a program that altered one token where
    it produced it, and went on from there, would have served."""
    out = [(rid, p, list(s)) for rid, p, s in served]
    j = max(range(len(out)), key=lambda i: len(out[i][2]))
    toks = out[j][2]
    k = len(toks) // 2
    toks[k] = (int(toks[k]) + 1) % cfg["vocab_size"]
    return out


def verdict(checks: Dict[str, Dict]) -> bool:
    """Correct when every compared number is at or under its limit."""
    return all(c["limit"] is not None and c["value"] is not None
               and c["value"] <= c["limit"] for c in checks.values())


def lines(checks: Dict[str, Dict]) -> List[str]:
    return [f"check {k}: {c['value']!r} limit {c['limit']!r}"
            for k, c in checks.items()]
