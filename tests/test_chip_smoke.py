"""``chip_smoke.py`` and the entry-point plumbing it shares with the
launchers: it refuses to run anywhere but on a TPU, its phases pass at
CPU size (reduced config, Pallas kernels interpreted), the serve CLI can
leave the reduced config, and the compile cache lands where it should."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("extra_env,reason", [
    ({}, "no TPU"),
    ({"REPRO_FORCE_PALLAS_INTERPRET": "1"}, "REPRO_FORCE_PALLAS_INTERPRET"),
], ids=["cpu", "interpret-env"])
def test_chip_smoke_refuses_off_chip(extra_env, reason):
    env = {k: v for k, v in os.environ.items()
           if k not in ("REPRO_FORCE_PALLAS_INTERPRET", "REPRO_USE_KERNELS")}
    env.update(JAX_PLATFORMS="cpu", **extra_env)
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert reason in proc.stderr
    assert '"ok"' not in proc.stdout


def test_chip_smoke_phases_at_cpu_size(smoke, monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_PALLAS_INTERPRET", "1")
    argv = ["--arch", "qwen3-0.6b", "--engine", "paged", "--requests", "4",
            "--prompt-len", "32", "--max-new", "4", "--cache-max", "64",
            "--num-blocks", "64", "--block-size", "8"]
    model, params, prompts, toks = smoke.serving_phase(argv)
    assert len(toks) == 4 and all(len(t) == 4 for t in toks)
    smoke.decode_kernel_phase(argv, model, params, prompts, toks)
    smoke.kernels_phase({"attn": (2, 4, 2, 64, 64, 17, 8, 4),
                         "rwkv": (1, 2, 40, 16), "conv": (1, 3)})
    smoke.digits_phase(rounds=6, train_n=2_000)


def test_serve_cli_reduced_switch():
    from repro.launch.serve import build_parser

    assert build_parser().parse_args([]).reduced is True
    assert build_parser().parse_args(["--no-reduced"]).reduced is False
    assert build_parser().parse_args(["--reduced"]).reduced is True


@pytest.mark.parametrize("env_dir", [None, "cache-dir-from-env"],
                         ids=["unset", "set"])
def test_compile_cache_placement(env_dir, monkeypatch):
    """Unset: the fixed in-checkout directory.  Set: JAX's own setting
    from the environment is left alone."""
    import jax

    from repro.launch.compile_cache import (CHECKOUT_CACHE,
                                            configure_compile_cache)

    prev = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        jax.config.update("jax_compilation_cache_dir", "untouched")
        got = configure_compile_cache()
        if env_dir is None:
            assert got == str(CHECKOUT_CACHE) == str(ROOT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == "untouched"
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_chip_smoke_check_raises(smoke):
    with pytest.raises(smoke.SmokeFailure, match="boom"):
        smoke.check(False, "boom")
