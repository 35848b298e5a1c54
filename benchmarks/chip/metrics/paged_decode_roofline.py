"""``paged_flash_decode``'s share of its roofline on the chip.

The least time the chip could take for the calls the window made (the
larger of bytes over HBM bandwidth and operations over peak FLOP/s, with
bytes and operations from the calls' shapes, below) over the kernel's
device time in the trace.  A call reads every row's query and writes its
output, and reads each page that holds one of the row's live K/V slots
once (the kernel moves whole pages); it computes q.k and p.v over the
live slots.  Returns nothing when the trace holds no call."""
import math

KERNEL = "paged_flash_decode"


def itemsize(dtype: str) -> int:
    return {"float32": 4, "bfloat16": 2, "float16": 2}[dtype]


def call_cost(cfg: dict, lengths, page: int):
    """(bytes, flops) of one call over rows whose KV lengths (slots the
    row attends over, its own new slot included) are ``lengths``."""
    kv, dh = cfg["n_kv_heads"], cfg["head_dim"]
    heads = cfg["n_heads"]
    item = itemsize(cfg["dtype"])
    rows = len(lengths)
    q_and_out = 2 * rows * heads * dh * item
    pages = sum(math.ceil(n / page) for n in lengths)
    kv_bytes = 2 * pages * page * kv * dh * item
    flops = 4 * heads * dh * sum(lengths)
    return q_and_out + kv_bytes, flops


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    k = run.trace["kernels"].get(KERNEL)
    if not k or not k["calls"] or k["seconds"] <= 0:
        return None
    cfg, page = run.cell.config, run.cell.traffic["page_size"]
    layers = cfg["num_layers"]
    calls, nbytes, flops = 0, 0, 0
    for r in run.rounds:
        if r.lengths:
            b, f = call_cost(cfg, r.lengths, page)
            calls += layers
            nbytes += layers * b
            flops += layers * f
    if not calls:
        return None
    # per call, should the trace hold more or fewer calls than the rounds
    scale = k["calls"] / calls
    t_min = max(nbytes * scale / run.peaks["hbm_bytes_per_s"],
                flops * scale / run.peaks["bf16_flops_per_s"])
    return t_min / k["seconds"] * 100.0
