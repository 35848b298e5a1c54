"""Pipeline-Planner walkthrough: schedule table across memory budgets
(the paper's Fig. 6b/Fig. 7 flow) for any config in the registry.

    PYTHONPATH=src python examples/plan_budgets.py --arch gpt2_base
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax

from repro.checkpoint import ensure_partitioned
from repro.configs import get_config
from repro.core import Hermes
from repro.models.api import build_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2_base")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale variant (fast)")
    ap.add_argument("--quant", action="store_true",
                    help="search shard dtype (fp32/int8/int4) jointly "
                    "with the schedule")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced().with_(num_layers=8)
    ckpt = Path(f"/tmp/repro_plan_{cfg.name.replace('.', '_')}")
    ensure_partitioned(ckpt, cfg,
                       lambda: build_model(cfg).init(jax.random.PRNGKey(0)))

    h = Hermes(ckpt, cfg)
    prof = h.profile()
    lb, other = prof["layer_bytes"], prof["other_bytes"]
    budgets = [other + k * lb for k in (2, 3, 4, 6, 8, 12)] + [None]
    quants = ("fp32", "int8", "int4") if args.quant else None
    print(f"{'budget':>12} {'agents':>7} {'dtype':>6} "
          f"{'pred latency':>13} {'pred peak':>10}")
    for b, e in zip(budgets, h.plan(budgets, quants=quants)):
        bs = "unlimited" if b is None else f"{b/2**20:.0f}MB"
        print(f"{bs:>12} {e.num_agents:>7} {e.dtype or 'fp32':>6} "
              f"{e.predicted_latency_s*1e3:>10.1f}ms "
              f"{e.predicted_peak_bytes/2**20:>8.1f}MB")


if __name__ == "__main__":
    main()
