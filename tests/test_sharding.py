"""Sharding-spec tests + a miniature-mesh integration dry-run.

The mini dry-run runs in a SUBPROCESS with 8 host devices so the main test
process keeps its single-device backend (the dry-run contract).
"""
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.launch.train import abstract_params
from repro.sharding.specs import cache_pspecs, param_pspecs


@pytest.mark.parametrize("arch", ["qwen3-32b", "qwen3-moe-235b-a22b",
                                  "rwkv6-1.6b", "jamba-1.5-large-398b",
                                  "deepseek-v2-lite-16b"])
def test_param_specs_cover_all_leaves(arch):
    cfg = get_config(arch)
    shapes = abstract_params(cfg)
    specs = param_pspecs(cfg, shapes)
    s_leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    p_leaves = jax.tree_util.tree_leaves(shapes)
    assert len(s_leaves) == len(p_leaves)
    # the vast majority of weight bytes must actually be sharded
    sharded_bytes = total_bytes = 0
    for spec, leaf in zip(s_leaves, p_leaves):
        b = np.prod(leaf.shape) * leaf.dtype.itemsize
        total_bytes += b
        if any(ax is not None for ax in spec):
            sharded_bytes += b
    assert sharded_bytes / total_bytes > 0.95


@pytest.mark.parametrize("arch", ["qwen3-32b", "deepseek-v2-lite-16b",
                                  "jamba-1.5-large-398b"])
def test_param_specs_divisible_on_production_mesh(arch):
    """Every sharded dim must divide the (16,16) production mesh axes."""
    axis_size = {"data": 16, "model": 16, "pod": 2}
    cfg = get_config(arch)
    shapes = abstract_params(cfg)
    specs = param_pspecs(cfg, shapes)
    for spec, leaf in zip(
            jax.tree_util.tree_leaves(specs,
                                      is_leaf=lambda x: isinstance(x, P)),
            jax.tree_util.tree_leaves(shapes)):
        for dim, ax in zip(leaf.shape, tuple(spec)):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            n = int(np.prod([axis_size[a] for a in axes]))
            assert dim % n == 0, (arch, leaf.shape, tuple(spec))


def test_cache_specs_divisible():
    axis_size = {"data": 16, "model": 16, "pod": 2}
    from repro.launch.serve import abstract_cache
    for arch, shape_name in [("qwen3-32b", "decode_32k"),
                             ("rwkv6-1.6b", "long_500k"),
                             ("deepseek-v2-lite-16b", "long_500k"),
                             ("jamba-1.5-large-398b", "decode_32k")]:
        cfg = get_config(arch)
        shape = SHAPES[shape_name]
        cache = abstract_cache(cfg, shape)
        specs = cache_pspecs(cfg, cache, shape, multi_pod=False)
        for spec, leaf in zip(
                jax.tree_util.tree_leaves(
                    specs, is_leaf=lambda x: isinstance(x, P)),
                jax.tree_util.tree_leaves(cache)):
            for dim, ax in zip(leaf.shape, tuple(spec)):
                if ax is None:
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                n = int(np.prod([axis_size[a] for a in axes]))
                assert dim % n == 0, (arch, shape_name, leaf.shape,
                                      tuple(spec))


MINI_DRYRUN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, numpy as np
    from repro.compat import make_mesh
    from repro.configs import get_config
    from repro.configs.shapes import InputShape, input_specs
    from repro.launch.train import make_sharded_train_step, abstract_params
    from repro.sharding.activations import activation_sharding

    cfg = dataclasses.replace(
        get_config("llama3.2-3b").reduced(n_layers=2, d_model=128),
        param_dtype="float32")
    shape = InputShape("mini", 128, 8, "train")
    mesh = make_mesh((4, 2), ("data", "model"))
    with mesh, activation_sharding(mesh, ("data",)):
        step, _, _ = make_sharded_train_step(cfg, mesh, shape)
        lowered = step.lower(abstract_params(cfg), input_specs(cfg, shape))
        compiled = lowered.compile()
    txt = compiled.as_text()
    assert ("all-reduce" in txt) or ("all-gather" in txt)
    print("MINI_DRYRUN_OK")
""")


@pytest.mark.slow
def test_mini_mesh_dryrun_compiles():
    r = subprocess.run([sys.executable, "-c", MINI_DRYRUN],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "MINI_DRYRUN_OK" in r.stdout


def test_serve_step_donate_false_keeps_cache_readable():
    """`make_serve_step(donate=False)` must leave the caller's cache
    buffers alive: the serving gateway's TransformerBackend re-reads a
    cache it keeps by reference, so a silently donated buffer would
    poison the next dispatch of the same batch width."""
    import jax.numpy as jnp
    from repro.compat import make_mesh
    from repro.configs.shapes import InputShape
    from repro.launch.serve import make_serve_step
    from repro.models import transformer as T

    cfg = get_config("llama3.2-3b").reduced(n_layers=2, d_model=64)
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = InputShape("donate_smoke", 8, 2, "decode")
    step, _ = make_serve_step(cfg, mesh, shape, donate=False)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    cache = T.init_cache(cfg, 2, 8)
    tokens = jnp.zeros((2, 1), jnp.int32)

    logits, new_cache = step(params, cache, tokens, 0)
    # every original cache leaf is still materializable (not donated)
    for leaf in jax.tree_util.tree_leaves(cache):
        np.asarray(leaf)
    # and replaying from the ORIGINAL cache reproduces the step exactly
    logits2, _ = step(params, cache, tokens, 0)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(logits2))
