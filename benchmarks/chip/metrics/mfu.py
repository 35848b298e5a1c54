"""Model FLOP/s of the traced window over the chips' peak.

Operations the model needs for the tokens the window served: per decoded
token 2 x the matmul parameters it touches (every layer's projections and
MLP, and the output head) plus 4 x heads x head size per attended slot per
layer; per prefilled prompt of S tokens 2 x the layers' matmul parameters
x S, the head once, and the causal attention's S(S+1)/2 slot pairs.
Divided by the window and by chips x peak bf16 FLOP/s."""


def matmul_params(cfg: dict):
    """(per-layer matmul parameters summed over layers, head params)."""
    d, f = cfg["d_model"], cfg["d_ff"]
    hq = cfg["n_heads"] * cfg["head_dim"]
    hkv = cfg["n_kv_heads"] * cfg["head_dim"]
    p = cfg["vocab_pad_to"]
    vocab = (cfg["vocab_size"] + p - 1) // p * p
    mlp = (3 if cfg["gated_mlp"] else 2) * d * f
    per_layer = 2 * d * hq + 2 * d * hkv + mlp
    return cfg["num_layers"] * per_layer, d * vocab


def window_flops(cfg: dict, rounds) -> float:
    layers_p, head_p = matmul_params(cfg)
    att = 4 * cfg["n_heads"] * cfg["head_dim"] * cfg["num_layers"]
    total = 0.0
    for r in rounds:
        total += len(r.lengths) * 2 * (layers_p + head_p)
        total += att * sum(r.lengths)
        for s in r.prefill_lengths:
            total += 2 * layers_p * s + 2 * head_p + att * s * (s + 1) / 2
    return total


def read(run):
    if run.peaks is None:
        return None
    flops = window_flops(run.cell.config, run.rounds)
    return (flops / run.window_s
            / (run.chips * run.peaks["bf16_flops_per_s"]) * 100.0)
