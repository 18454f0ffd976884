"""``repro.compat.setup_compile_cache``: where JAX's persistent
compilation cache goes.

Each case runs in a subprocess, because the cache directory is process
state that would follow every later test of this worker.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

PROBE = textwrap.dedent("""
    import json, sys
    import jax, jax.numpy as jnp
    import repro.compat as compat
    checkout_dir, compile_one = sys.argv[1], sys.argv[2] == "compile"
    if checkout_dir != "-":
        # stand-in for the checkout's own directory, so the test writes
        # nothing into the tree it runs from
        compat.CHECKOUT_CACHE_DIR = compat.Path(checkout_dir)
    said = compat.setup_compile_cache()
    if compile_one:
        # cache every program, however quick its compile
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)(jnp.arange(7.0))
    print(json.dumps({"said": said,
                      "dir": jax.config.jax_compilation_cache_dir}))
""")


def _probe(tmp_path, checkout_dir=None, env_dir=None, compile_one=True):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src")] + ([env["PYTHONPATH"]]
                               if env.get("PYTHONPATH") else []))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    argv = [str(checkout_dir) if checkout_dir is not None else "-",
            "compile" if compile_one else "nocompile"]
    r = subprocess.run([sys.executable, "-c", PROBE, *argv], env=env,
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_cache_honours_the_environment_variable(tmp_path):
    env_dir = tmp_path / "from_env"
    decoy = tmp_path / "checkout_cache"
    out = _probe(tmp_path, checkout_dir=decoy, env_dir=env_dir)
    assert "from JAX_COMPILATION_CACHE_DIR" in out["said"]
    assert out["dir"] == str(env_dir)
    assert any(env_dir.iterdir())          # entries landed there
    assert not decoy.exists()              # and nowhere else


def test_cache_entries_land_in_the_checkout_directory(tmp_path):
    stand_in = tmp_path / "checkout_cache"
    out = _probe(tmp_path, checkout_dir=stand_in)
    assert "in-checkout default" in out["said"]
    assert out["dir"] == str(stand_in)
    assert any(stand_in.iterdir())


def test_cache_default_is_the_fixed_checkout_path(tmp_path):
    # no temp name, pid or time: two fresh processes, started from
    # different directories, choose <checkout>/.jax_cache
    dirs = []
    for i in range(2):
        (tmp_path / str(i)).mkdir()
        dirs.append(_probe(tmp_path / str(i), compile_one=False)["dir"])
    assert dirs == [str(ROOT / ".jax_cache")] * 2
