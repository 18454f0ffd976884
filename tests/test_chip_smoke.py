"""Rehearsals of ``chip_smoke.py`` on the CPU, at tiny sizes.

The script runs on a TPU only.  These tests drive its phases here with
the platform check stubbed in the test: the one-chip path (train, check
the aggregate, serve) in this process, and the ``--chips 4`` path on four
virtual host devices in a subprocess.  A plain run on the CPU must fail
without printing a result.
"""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"
TINY_FL = dict(dataset="mnist", n_devices=4, n_air=1, h_local=1,
               batch_cap=8, cohort_batch_align=8, train_fraction=0.01,
               eval_size=64, execution="batched", seed=0)


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cpu_device(n_chips):
    return {"platform": "cpu", "kind": "cpu", "count": n_chips}


def test_one_chip_phases_run_at_tiny_size(monkeypatch, capsys):
    import repro.compat
    smoke = _load_smoke()
    monkeypatch.setattr(smoke, "require_tpu", _cpu_device)
    monkeypatch.setattr(smoke, "FL", TINY_FL)
    monkeypatch.setattr(smoke, "SERVE_SECONDS", 30.0)
    # on the CPU the aggregate is the jnp reference: its dot stands where
    # the chip's program holds the kernel call
    monkeypatch.setattr(smoke, "KERNEL_MARKER", "dot(")
    monkeypatch.setattr(repro.compat, "setup_compile_cache",
                        lambda: "compile cache: left as it is")
    assert smoke.main([]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1]) == {"ok": True,
                                   "device": _cpu_device(1)}
    text = "\n".join(out)
    assert "round 0: wall" in text and "(includes compilation)" in text
    assert f"round {smoke.ROUNDS - 1}: wall" in text
    assert "bucket dispatches so far:" in text
    assert "matches the jnp reference" in text
    assert "serve: " in text


def test_plain_cpu_run_fails_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(SCRIPT)], capture_output=True,
                       text=True, timeout=300, env=env, cwd=ROOT)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU found" in r.stderr


FOUR_CHIP_REHEARSAL = textwrap.dedent("""
    import importlib.util, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    smoke.require_tpu = lambda n: {"platform": "cpu", "kind": "cpu",
                                   "count": n}
    smoke.FL = dict(FL_JSON)
    import repro.compat
    repro.compat.setup_compile_cache = lambda: "compile cache: unchanged"
    sys.exit(smoke.main(["--chips", "4"]))
""")


def test_four_chip_phase_on_four_virtual_devices():
    code = FOUR_CHIP_REHEARSAL.replace("FL_JSON", json.dumps(TINY_FL))
    r = subprocess.run([sys.executable, "-c", code, str(SCRIPT)],
                       capture_output=True, text=True, timeout=600,
                       cwd=ROOT, env=dict(os.environ))
    assert r.returncode == 0, r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    assert "mesh: shards=4" in r.stdout
    assert "off: shards=1" in r.stdout
    assert "off, 4 client slices per bucket" in r.stdout
    assert "sharded == single-device at the shard width" in r.stdout
    assert "mesh vs the whole-bucket single-device round" in r.stdout
    assert json.loads(lines[-1])["device"]["count"] == 4


@pytest.mark.parametrize("argv", [["--chips", "2"], ["--bogus"]])
def test_bad_arguments_are_refused(argv):
    smoke = _load_smoke()
    with pytest.raises(SystemExit) as e:
        smoke.main(argv)
    assert e.value.code == 2
