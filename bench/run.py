"""Run one benchmark cell once and print its result.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a host whose chips the cell asks for. The
cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``); its limits for the output check are in
``bench/limits/<cell>.json``, and each metric's reader is
``bench/metrics/<metric>.py``. With ``--trace 0`` the result carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
in a run traced by the JAX profiler.

Earlier lines describe the run; the last line of standard output is one
JSON object with ``correct``, ``attempted``, ``failed``, ``metrics`` and
``device`` (and ``breakdown`` when traced), and the numbers compared by the
output check, each with its limit, come last in it and last on standard
error. It exits non-zero without a result when JAX finds no TPU or fewer
chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _say(label, value):
    print(f"{label}: {value}", flush=True)


def device_or_exit(chips: int) -> dict:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s) "
              f"({devices[0].device_kind})", file=sys.stderr)
        raise SystemExit(2)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": chips}


def metrics_for(spec: dict, cell: str, trace: bool) -> list:
    group = spec["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def read_metrics(metrics: list, ctx) -> dict:
    out = {}
    for m in metrics:
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def breakdown(ctx) -> dict:
    """The device programs that took most time, and the device's idle by
    the program's own innermost host span (nine labels and
    ``uncovered``)."""
    from bench import program_trace as PT
    mods = sorted(ctx.trace.modules.items(), key=lambda kv: -kv[1][1])[:10]
    return {"device_ops": [[n, s] for n, (_, s) in mods],
            "idle_gaps": PT.top(ctx.program_trace.idle_by_program, 9)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"bench: no workload {args.workload!r} in "
                         "BENCHMARK.json")
    cell = cells[args.workload]
    device = device_or_exit(cell["chips"])
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        import repro  # noqa: F401  (the system under test)
    except ImportError as e:
        raise SystemExit(f"bench: the program is missing: {e}")
    from bench import harness as H
    from bench import window as Wn
    from bench.metrics.kv_tier_mb_per_req import KEYS as KV_MOVES

    cfg = H.load_config(cell["config"])
    mix = json.loads((ROOT / "bench" / "traffic"
                      / f"{cell['traffic']}.json").read_text())
    limits = json.loads((ROOT / "bench" / "limits"
                         / f"{args.workload}.json").read_text())
    _say("device", json.dumps(device))
    _say("cell", f"{args.workload} config={cell['config']} "
         f"traffic={cell['traffic']} seed={args.seed} "
         f"seconds={args.seconds} trace={args.trace}")
    ctx = H.run(cfg, mix, seed=args.seed, seconds=args.seconds,
                trace=bool(args.trace), t_start=T_START, limits=limits)

    due = Wn.due(ctx)
    started = [r for r in due if ctx.rec.tokens.get(r.rid)]
    _say("rate_rps", mix["arrival"]["rate_rps"])
    _say("requests", json.dumps({
        "due": len(due), "admitted": len(Wn.admitted(ctx)),
        "started": len(started), "failed": len(due) - len(started),
        "timed": len(due)}))
    _say("gaps_in_window", len(Wn.gaps_in_window(ctx)))
    _say("tokens_in_window", Wn.tokens_in_window(ctx))
    _say("decode_steps_in_window",
         len(Wn.steps_in_window(ctx.rec.decode, ctx)))
    _say("compiles_in_window", json.dumps(
        {"traces": ctx.compiles.traces, "compiles": ctx.compiles.compiles,
         "logged": ctx.compiles.names[:20]}))
    _say("release_lateness", json.dumps(Wn.lateness(ctx)))
    _say("kv_and_prefix", json.dumps({
        "kv_bytes_moved": sum(ctx.kv1[k] - ctx.kv0[k] for k in KV_MOVES),
        "prefix_hit_tokens": (ctx.pre1["prefix_hit_tokens"]
                              - ctx.pre0["prefix_hit_tokens"])
        if ctx.pre0 is not None else None,
        "prompt_tokens_admitted": sum(r.prompt_len
                                      for r in Wn.admitted(ctx))}))
    _say("stopped_after_window_s", ctx.stopped_at - ctx.window_end)
    _say("device_bytes_in_use_before_reference", ctx.bytes_in_use_after_free)
    if ctx.recorder is not None:
        _say("program_trace", json.dumps({
            "events": ctx.recorder.total_events,
            "dropped": ctx.recorder.dropped_events,
            "reduce_s": ctx.trace_reduce_s}))

    metrics = read_metrics(metrics_for(spec, args.workload,
                                       bool(args.trace)), ctx)
    dev = dict(device, memory_peak_bytes=ctx.memory_peak_bytes)
    if ctx.trace is not None:
        dev.update(busy_s=ctx.trace.busy_s, window_s=ctx.trace.window_s)
    checks = ctx.checks
    result = {"correct": H.correct(checks), "attempted": len(due),
              "failed": len(due) - len(started), "metrics": metrics,
              "device": dev}
    if ctx.trace is not None:
        result["breakdown"] = breakdown(ctx)
    _say("checked", json.dumps({"requests": checks["requests_checked"],
                                "tokens": checks["tokens_checked"]}))
    compared = {k: v for k, v in checks.items() if isinstance(v, dict)}
    result["checks"] = compared
    for name, v in compared.items():
        print(f"check {name}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
