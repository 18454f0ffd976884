"""``benchmarks/cohort_scaling.py``'s sharded rows: every worker runs on
virtual CPU devices, and a failed worker fails the module."""
import argparse
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import cohort_scaling  # noqa: E402


def _args():
    return argparse.Namespace(sharded_devices=[1, 2], smoke=True,
                              cohorts=None, rounds=None, payload="mlp",
                              h_local=1, batch_cap=8)


def test_failed_sharded_worker_fails_the_module(monkeypatch):
    seen = []

    def failing_run(cmd, env, **kw):
        seen.append(env)
        return subprocess.CompletedProcess(cmd, 1, stdout="",
                                           stderr="worker crashed")

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(subprocess, "run", failing_run)
    assert cohort_scaling._sharded_rows(_args()) == 1
    # the first failure stops the sweep; the worker was pinned to the CPU
    # even though the parent's environment named the TPU
    assert len(seen) == 1
    assert seen[0]["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=1" in seen[0]["XLA_FLAGS"]
