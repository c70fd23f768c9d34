"""Find a cell's knee: the highest arrival rate the served path sustains
(by hand, on the chip, when a cell is defined; not part of a benchmark run).

    python bench/sweep.py --workload qwen14b-docqa --rates 0.3,0.5,0.7 \\
        --seconds 25 --seed 901 --out sweep.json

Runs the cell as ``bench/run.py`` does, once per rate with the mix's
``rate_rps`` replaced, all in one process, and prints one line per rate:
requests due, time to first token (the median, and the median of the
first and the last quarter of arrivals: a queue that grows through the
window shows as a later quarter far above the first), the gap tail, tokens
per second, the share that met the service limits and the mean decode rows.
"""
import argparse
import copy
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    type=lambda s: [float(x) for x in s.split(",")])
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=901)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness as H
    from bench import window as Wn
    from bench.metrics import (batch_rows_mean, out_tokens_per_s,
                               slo_met_share, tbt_p95_ms, ttft_p50_s)
    spec = H.load_spec(ROOT)
    cell = {w["name"]: w for w in spec["workloads"]}[args.workload]
    cfg = H.load_config(cell["config"])
    base = json.loads((ROOT / "bench" / "traffic"
                       / f"{cell['traffic']}.json").read_text())
    limits = json.loads((ROOT / "bench" / "limits"
                         / f"{args.workload}.json").read_text())
    out = []
    for rate in args.rates:
        mix = copy.deepcopy(base)
        mix["arrival"]["rate_rps"] = rate
        t0 = time.perf_counter()
        ctx = H.run(cfg, mix, seed=args.seed, seconds=args.seconds,
                    trace=False, t_start=t0, limits=limits)
        tt = Wn.ttfts(ctx)
        q = max(len(tt) // 4, 1)
        rec = {"rate_rps": rate, "due": len(tt),
               "ttft_p50_s": ttft_p50_s.read(ctx),
               "ttft_first_quarter_s": float(np.median(tt[:q])),
               "ttft_last_quarter_s": float(np.median(tt[-q:])),
               "tbt_p95_ms": tbt_p95_ms.read(ctx),
               "out_tokens_per_s": out_tokens_per_s.read(ctx),
               "slo_met_share": slo_met_share.read(ctx),
               "batch_rows_mean": batch_rows_mean.read(ctx),
               "setup_s": ctx.setup_s,
               "compiles_in_window": ctx.compiles.traces,
               "correct": H.correct(ctx.checks),
               "run_s": time.perf_counter() - t0}
        out.append(rec)
        print(json.dumps(rec), flush=True)
        Path(args.out).write_text(json.dumps(out, indent=1))
        del ctx
    return 0


if __name__ == "__main__":
    sys.exit(main())
