"""Mesh-native FL pieces: hierarchical weighted psum (eq. 13 on the mesh)
and the multi-pod FL train step (subprocess with 8 host devices)."""
import subprocess
import sys
import textwrap

import pytest

PSUM_TEST = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.compat import make_mesh, shard_map
    from repro.fl.aggregation import hierarchical_weighted_psum
    from repro.launch.train import make_replica_agg_step

    mesh = make_mesh((2, 4), ("pod", "data"))
    # each (pod, data) shard holds its own "client model" scalar
    vals = jnp.arange(8, dtype=jnp.float32).reshape(2, 4)

    def agg(v):
        lam = 1.0 / 8.0
        return hierarchical_weighted_psum({"w": v}, lam,
                                          ("data", "pod"))["w"]

    out = jax.jit(shard_map(agg, mesh=mesh, in_specs=P("pod", "data"),
                            out_specs=P("pod", "data")))(vals)
    expected = float(np.mean(np.arange(8)))
    assert np.allclose(np.asarray(out), expected), (out, expected)

    # same aggregation through the packaged shard_map helper
    lam = jnp.full((2, 4), 1.0 / 8.0)
    step = make_replica_agg_step(mesh, ("data", "pod"), P("pod", "data"))
    out2 = step({"w": vals}, lam)["w"]
    assert np.allclose(np.asarray(out2), expected), (out2, expected)
    print("PSUM_OK")
""")

FL_STEP_TEST = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.compat import make_mesh
    from repro.configs.shapes import InputShape
    from repro.launch.train import make_fl_train_step, abstract_params
    from repro.models import transformer as T

    cfg = dataclasses.replace(
        get_config("olmo-1b").reduced(n_layers=2, d_model=128),
        param_dtype="float32")
    shape = InputShape("mini", 64, 8, "train")
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    with mesh:
        step, rep_sh, batch_sh = make_fl_train_step(cfg, mesh, shape,
                                                    lr=1e-2, h_local=2)
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        rep = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (2,) + x.shape), params)
        rng = np.random.default_rng(0)
        batch = {
            "inputs": jnp.asarray(rng.integers(0, cfg.vocab_size,
                                               (2, 4, 64)), jnp.int32),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab_size,
                                               (2, 4, 64)), jnp.int32),
        }
        rep = jax.device_put(rep, rep_sh)
        batch = {k: jax.device_put(v, batch_sh[k]) for k, v in batch.items()}
        new_rep, metrics = step(rep, batch)
    # aggregated replicas must be identical across the pod axis
    for leaf in jax.tree_util.tree_leaves(new_rep):
        a = np.asarray(leaf)
        assert np.allclose(a[0], a[1], atol=1e-5)
    assert np.isfinite(float(metrics["loss"]))
    print("FL_STEP_OK")
""")


@pytest.mark.slow
def test_hierarchical_psum_matches_mean():
    r = subprocess.run([sys.executable, "-c", PSUM_TEST],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "PSUM_OK" in r.stdout


@pytest.mark.slow
def test_fl_train_step_aggregates_replicas():
    r = subprocess.run([sys.executable, "-c", FL_STEP_TEST],
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "FL_STEP_OK" in r.stdout
