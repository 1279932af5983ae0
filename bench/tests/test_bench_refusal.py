"""The command measures only the chip: anywhere else it refuses, with exit
code 2 and no result line."""
import json
import os
import shutil
import subprocess
import sys

from bench.harness.device import ROOT

ARGS = ["bench/run.py", "--workload", "lastfm.train.bts",
        "--seed", "2147483777", "--seconds", "1", "--trace", "0"]


def _run(cwd, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    full.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable] + ARGS, cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=120)


def _no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


def test_refuses_off_the_tpu():
    res = _run(ROOT)
    assert res.returncode == 2, res.stderr
    assert "no TPU" in res.stderr and "cpu" in res.stderr
    assert _no_result(res.stdout)


def test_refuses_with_kernels_routed_to_their_oracles():
    res = _run(ROOT, REPRO_FORCE_REF="1")
    assert res.returncode == 2
    assert "REPRO_FORCE_REF" in res.stderr
    assert _no_result(res.stdout)


def test_refuses_in_a_directory_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    res = _run(tmp_path)
    assert res.returncode == 2
    assert "src/repro" in res.stderr
    assert _no_result(res.stdout)
