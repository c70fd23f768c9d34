"""Readings that the output check's limits are set from (by hand, on the
chip; not part of a benchmark run).

    python bench/control.py --workload qwen14b-docqa --seeds 1101-1106 \\
        --control-seeds 1107-1109 --seconds 20 --out readings.json

Each seed runs the cell as ``bench/run.py`` does (``harness.run`` with the
cell's limits in ``bench/limits/<cell>.json``, then ``harness.correct``),
with a window of ``--seconds``. The ``--seeds`` run the program as the
configuration states it: the largest of their numbers is each number's lower
reading. The ``--control-seeds`` run the control: the same program at its
own default matmul precision (one bfloat16 pass for float32 on a TPU, the
precision ``launch/server.py`` serves at) in place of the configuration's
``highest``; the smallest of their numbers is the upper reading, and each
of these runs has to come out not correct. All runs share one process, so
each precision's programs are compiled once. (The faults a served cell can
have are checked by ``bench/tests/test_faults.py``.)
"""
import argparse
import copy
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
CONTROL_PRECISION = "default"


def seeds_arg(text: str) -> list:
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b or a) + 1))
    return out


def as_control(cfg: dict) -> dict:
    """The configuration with the program at its own default precision."""
    c = copy.deepcopy(cfg)
    c["assumed"]["matmul_precision"] = CONTROL_PRECISION
    return c


def reading(H, cfg, mix, seed, seconds, limits) -> dict:
    t0 = time.perf_counter()
    ctx = H.run(cfg, mix, seed=seed, seconds=seconds, trace=False,
                t_start=t0, limits=limits)
    c = ctx.checks
    return {"seed": seed,
            "precision": cfg["assumed"]["matmul_precision"],
            "correct": H.correct(c),
            **{k: v["value"] for k, v in c.items() if isinstance(v, dict)},
            "tokens": c["tokens_checked"], "requests": c["requests_checked"],
            "compiles_in_window": ctx.compiles.traces,
            "memory_peak_bytes": ctx.memory_peak_bytes,
            "run_s": time.perf_counter() - t0}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds_arg, default=[])
    ap.add_argument("--control-seeds", type=seeds_arg, default=[])
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness as H
    spec = H.load_spec(ROOT)
    cell = {w["name"]: w for w in spec["workloads"]}[args.workload]
    cfg = H.load_config(cell["config"])
    mix = json.loads((ROOT / "bench" / "traffic"
                      / f"{cell['traffic']}.json").read_text())
    limits = json.loads((ROOT / "bench" / "limits"
                         / f"{args.workload}.json").read_text())
    out = {"workload": args.workload, "seconds": args.seconds,
           "program": [], "control": []}
    runs = [("program", cfg, s) for s in args.seeds] + \
        [("control", as_control(cfg), s) for s in args.control_seeds]
    for kind, c, seed in runs:
        rec = reading(H, c, mix, seed, args.seconds, limits)
        out[kind].append(rec)
        print(json.dumps(dict(rec, kind=kind)), flush=True)
        Path(args.out).write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
