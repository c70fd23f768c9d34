"""The launcher surface on the CPU: ``server --layers``, ``server.main``
returning its report, ``chip_smoke.py``'s serve-and-check phase at a tiny
size and its device check, and the compile-cache helper."""
import argparse
import dataclasses
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs.base import get_config
from repro.launch import jax_cache, server

ROOT = Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def no_persistent_cache(monkeypatch, tmp_path):
    """An outside cache dir makes the helper set nothing, so the launchers
    run here without turning on the persistent cache for this worker."""
    monkeypatch.setenv(jax_cache.ENV_VAR, str(tmp_path / "jax_cache"))


def test_layers_cuts_depth_only():
    args = argparse.Namespace(arch="qwen2.5-14b", tiny=False, layers=2)
    full = get_config("qwen2.5-14b")
    cut = server.build_config(args)
    assert cut.num_layers == 2
    assert cut == dataclasses.replace(full, num_layers=2)
    assert (cut.d_model, cut.num_heads, cut.num_kv_heads, cut.head_dim,
            cut.d_ff, cut.vocab_size) == (5120, 40, 8, 128, 13824, 152064)
    args.layers = None
    assert server.build_config(args) == full


@pytest.mark.parametrize("argv", [
    ["--arch", "qwen2.5-14b", "--tiny", "--layers", "1"],
    ["--paper-model", "llama-7b", "--layers", "1"],
    ["--arch", "qwen2.5-14b", "--layers", "0"],
])
def test_layers_refused_outside_a_published_arch(argv, capsys,
                                                  no_persistent_cache):
    with pytest.raises(SystemExit) as e:
        server.main(argv)
    assert e.value.code == 2
    assert "--layers" in capsys.readouterr().err


def test_main_returns_the_printed_report(capsys, no_persistent_cache):
    out = server.main(["--paper-model", "llama-7b", "--requests", "3",
                       "--max-batch", "2", "--gen-len", "2", "3",
                       "--prompt-len", "4", "8"])
    assert json.loads(capsys.readouterr().out) == json.loads(
        json.dumps(out, default=float))
    assert out["summary"]["requests"] == 3
    assert out["failures"] == []
    assert out["wall"]["run_s"] > 0.0
    assert "streams" not in out            # analytic: no real tokens


def test_chip_smoke_serve_and_check_tiny(capsys, no_persistent_cache):
    cs = _chip_smoke()
    cfg = get_config("qwen2.5-14b", tiny=True)
    out = cs.serve_and_check(["--arch", "qwen2.5-14b", "--tiny"], cfg)
    assert cs.check(out, cfg.vocab_size) == []
    assert out["kv"]["kv_swap_out_bytes"] > 0
    assert out["kv"]["kv_ssd_write_bytes"] > 0
    assert out["prefix"]["prefix_hit_tokens"] > 0
    assert out["prefix"]["prefix_restored_tokens"] > 0
    assert len(out["streams"]) == cs.REQUESTS
    # the check catches a short, out-of-vocabulary or non-finite stream
    bad = dict(out, streams=[dict(out["streams"][0], tokens=[cfg.vocab_size],
                                  logits_finite=False)])
    problems = " ".join(cs.check(bad, cfg.vocab_size))
    for what in ("requests answered", "tokens", "vocabulary", "non-finite"):
        assert what in problems


def test_chip_smoke_refuses_a_cpu_device():
    cs = _chip_smoke()
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit, match="no TPU"):
        cs.device_check()
    # run as the script, it exits non-zero and prints no result
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          capture_output=True, text=True, env=env,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv(jax_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert jax_cache.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv(jax_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = jax_cache.setup_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
