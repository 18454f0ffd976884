"""A measurement never falls back to the CPU: with no TPU, or in a checkout
that holds only the benchmark, a run exits non-zero and prints no result."""
import json
import os
import shutil
import subprocess
import sys

from bench.harness import core


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "mnist-cnn.train.multi_region", "--seed", "3", "--seconds", "1",
         "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _printed_a_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            continue
    return False


def test_no_tpu_exits_nonzero_with_no_result():
    out = _run(core.ROOT)
    assert out.returncode != 0
    assert not _printed_a_result(out.stdout)
    assert "no TPU" in out.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copytree(core.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(core.ROOT / "BENCHMARK.json", tmp_path)
    out = _run(tmp_path)
    assert out.returncode != 0
    assert not _printed_a_result(out.stdout)
    assert "no program" in out.stderr


def test_the_chip_check_refuses_a_cpu_and_too_few_chips(monkeypatch):
    import jax
    import pytest
    with pytest.raises(core.BenchError, match="no TPU"):
        core.require_tpu(1)

    class Chip:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda: [Chip()])
    assert core.require_tpu(1) == {"platform": "tpu",
                                   "kind": "TPU v5 lite", "count": 1}
    with pytest.raises(core.BenchError, match="needs 4 chips, 1 visible"):
        core.require_tpu(4)
