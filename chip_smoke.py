"""Smoke test of the served M2Cache path on one TPU chip.

Run from the root of a checkout, on a host with a TPU:

    python chip_smoke.py

It drives the server's own entry point (``repro.launch.server.main``) in
this one process: Qwen2.5-14B at its published widths, depth cut to two
layers, random float32 weights from a seed, chat traffic that shares
system prompts, the radix prefix cache on, and KV budgets small enough that
the tiered cache demotes real KV bytes from HBM to host DRAM and on to SSD
and restores cached prefix blocks. It fails (non-zero exit) when JAX finds
no TPU, when the repo's code is missing, when any request fails, falls
short of its tokens, emits a token outside the vocabulary or ends with
non-finite logits, or when the tiers moved nothing. On success the last
line of standard output is the JSON object
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Times printed here are on the host clock; the scheduler's modeled-clock
figures are printed only under names that say "modeled".
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent

ARCH = "qwen2.5-14b"
LAYERS = 2            # published widths; the depth that fits one v5e in f32
REQUESTS = 8
GEN_LEN = (8, 16)
MAX_BATCH = 4
PREFILL_CHUNK = 16    # the engine's KV block
# KV budgets in tokens of KV: the HBM tier holds about two requests' KV
# (prompts are 72-88 tokens, generations 8-16), so a batch of four pages;
# the DRAM tier holds less than one request, so demoted blocks spill to SSD
HBM_KV_TOKENS = 224
DRAM_KV_TOKENS = 64


def device_check() -> dict:
    """The device JAX sees; anything but a TPU ends the run."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU: JAX found {dev.platform} "
                         f"({dev.device_kind})")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def serve_argv(model_argv, kv_bytes_per_token: float) -> list:
    """The server's command line for the smoke run."""
    gib = 2.0 ** 30
    return list(model_argv) + [
        "--workload", "shared-prefix", "--prefix-cache",
        "--prefix-groups", "2", "--prefix-reuse", "1.0",
        "--requests", str(REQUESTS), "--rate", "1e9",
        "--gen-len", *map(str, GEN_LEN),
        "--max-batch", str(MAX_BATCH),
        "--prefill-chunk", str(PREFILL_CHUNK),
        "--hbm-kv-gb", repr(HBM_KV_TOKENS * kv_bytes_per_token / gib),
        "--dram-kv-gb", repr(DRAM_KV_TOKENS * kv_bytes_per_token / gib),
        "--seed", "0"]


def check(out: dict, vocab_size: int) -> list:
    """What is wrong with one server report (empty when nothing is)."""
    problems = []
    if out["failures"]:
        problems.append(f"failed requests: {out['failures']}")
    streams = out["streams"]
    if len(streams) != REQUESTS:
        problems.append(f"{len(streams)} of {REQUESTS} requests answered")
    for s in streams:
        if len(s["tokens"]) < s["max_new_tokens"]:
            problems.append(f"request {s['rid']}: {len(s['tokens'])} of "
                            f"{s['max_new_tokens']} tokens")
        if any(not 0 <= t < vocab_size for t in s["tokens"]):
            problems.append(f"request {s['rid']}: token outside the "
                            f"vocabulary: {s['tokens']}")
        if not s["logits_finite"]:
            problems.append(f"request {s['rid']}: non-finite logits")
    kv, prefix = out["kv"], out["prefix"]
    for name, value in (("KV demotion bytes", kv["kv_swap_out_bytes"]),
                        ("KV SSD write bytes", kv["kv_ssd_write_bytes"]),
                        ("real KV payload blocks",
                         kv["kv_real_payload_blocks"]),
                        ("prefix hit tokens", prefix["prefix_hit_tokens"]),
                        ("restored prefix tokens",
                         prefix["prefix_restored_tokens"])):
        if not value > 0:
            problems.append(f"{name} is {value}: the tiers moved nothing")
    return problems


def serve_and_check(model_argv, cfg) -> dict:
    """Serve the smoke traffic through ``server.main`` for the model that
    ``model_argv`` selects (``cfg`` is its config) and check the report.
    Raises SystemExit on any problem; returns the report."""
    from repro.core.kv_payload import token_nbytes
    from repro.launch import server
    from repro.models import transformer as T
    import jax.numpy as jnp
    per_token = token_nbytes(T.cache_specs(cfg, 1, max_seq=32,
                                           dtype=jnp.float32))
    out = server.main(serve_argv(model_argv, per_token))
    problems = check(out, cfg.vocab_size)
    if problems:
        raise SystemExit("chip_smoke: " + "; ".join(problems))
    return out


def _line(label, value):
    print(f"{label}: {value}", flush=True)


def main() -> int:
    device = device_check()
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.jax_cache import setup_compile_cache
    except ImportError as e:
        raise SystemExit(f"chip_smoke: the repo's code is missing: {e}")
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.base import get_config
    from repro.models import transformer as T

    _line("device_kind", device["kind"])
    _line("compile_cache_dir", setup_compile_cache())
    cfg = dataclasses.replace(get_config(ARCH), num_layers=LAYERS)
    param_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in jax.tree.leaves(T.abstract_params(
                          cfg, dtype=jnp.float32, m2=True)))
    _line("model", f"{ARCH} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} float32")
    _line("param_bytes", param_bytes)

    out = serve_and_check(["--arch", ARCH, "--layers", str(LAYERS)], cfg)

    wall, summ = out["wall"], out["summary"]
    rest_tokens = summ["total_tokens"] - wall["first_step_tokens"]
    rest_s = wall["run_s"] - wall["first_token_s"]
    _line("wall_s_to_first_token (compiles included)", wall["first_token_s"])
    _line("wall_s_rest", rest_s)
    _line("tokens_rest", rest_tokens)
    _line("wall_tokens_per_s_rest", rest_tokens / rest_s)
    _line("requests_answered", len(out["streams"]))
    stats = jax.devices()[0].memory_stats() or {}
    _line("peak_bytes_in_use", stats.get("peak_bytes_in_use"))
    kv = out["kv"]
    _line("kv_tiers", json.dumps({
        k: kv[k] for k in ("kv_swap_out_bytes", "kv_swap_in_bytes",
                           "kv_ssd_write_bytes", "kv_ssd_read_bytes",
                           "kv_ssd_blocks", "kv_real_payload_blocks",
                           "kv_preempt_swaps")}))
    _line("prefix", json.dumps({
        k: out["prefix"][k] for k in ("prefix_hit_tokens",
                                      "prefix_restored_tokens")}))
    _line("preemptions", summ["preemptions"])
    _line("modeled_span_s", summ["modeled_span_s"])
    _line("modeled_tokens_per_s", summ["tokens_per_s"])
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
