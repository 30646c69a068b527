"""The command refuses to measure off the chip, and without the program."""
import json
import os
import shutil
import subprocess
import sys

from tiny import ROOT

CMD = json.loads((ROOT / "BENCHMARK.json").read_text())["command"]
ARGS = ["--workload", "qwen3-0.6b.chat-prefix", "--seed", "2147483659",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable] + CMD[1:] + ARGS, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_refuses_a_cpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_fails_with_only_the_benchmark_files(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
