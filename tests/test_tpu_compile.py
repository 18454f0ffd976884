"""Compile-only tests for a described TPU v5e (no chip attached).

The TPU compiler is installed with jaxlib, and it compiles for a chip
that is described rather than present.  These tests compile the main
path's programs at real widths and check what interpret-mode tests
cannot: that the ``fedavg_agg`` kernel fits the chip's VMEM for every
cohort size the engine produces, and that a VGG-11 cohort round with
the kernel compiles for one chip.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, so under
several pytest workers only the worker that runs this file loads it.
The persistent compilation cache is off around the compiles: a program
compiled for a described chip can be written to it but not read back.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fedavg_agg import kernel as agg_kernel

KERNEL_MARKER = "tpu_custom_call"
#: VGG-11's largest leaf (512x512x3x3 conv) and a 512-element bias
LEAVES = {"vgg11_conv512": 2_359_296, "bias512": 512}
COHORTS = (4, 64, 256, 1024)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("clients", COHORTS)
@pytest.mark.parametrize("leaf", sorted(LEAVES))
def test_fedavg_agg_compiles_for_v5e(one_chip, leaf, clients):
    stacked = _spec((clients, LEAVES[leaf]), one_chip)
    weights = _spec((clients,), one_chip)
    compiled = agg_kernel.weighted_aggregate.lower(stacked,
                                                   weights).compile()
    assert KERNEL_MARKER in compiled.as_text()


def test_vgg11_cohort_round_compiles_for_v5e(one_chip, monkeypatch):
    """The fused single-bucket round (local update + eq.-(13) aggregate,
    params donated) of VGG-11 at a real bucket: 64 clients, H=5, B=32."""
    from repro.fl.client import cohort_round_step_donated
    from repro.kernels.fedavg_agg import ops
    from repro.models.cnn import apply_vgg11, init_vgg11

    # this process's default backend is the CPU; steer the aggregate's
    # dispatch to the chip's branch, as on the described device
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    c, h, b = 64, 5, 32
    params = jax.tree_util.tree_map(
        lambda a: _spec(a.shape, one_chip, a.dtype),
        jax.eval_shape(init_vgg11, jax.random.PRNGKey(0)))
    compiled = cohort_round_step_donated.lower(
        apply_vgg11, params,
        _spec((c, h, b, 32, 32, 3), one_chip),
        _spec((c, h, b), one_chip, jnp.int32),
        _spec((c, h, b), one_chip),
        _spec((c,), one_chip),
        _spec((), one_chip)).compile()
    assert KERNEL_MARKER in compiled.as_text()
    mem = compiled.memory_analysis()
    # the whole round fits one v5e chip's 16 GB of HBM
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < 16e9


def test_fedavg_agg_compiles_inside_shard_map_for_v5e_2x2(topo, monkeypatch):
    """The sharded cohort's in-mesh aggregate: the kernel runs per chip
    inside ``shard_map`` (varying-axis checks on) and the partial sums
    combine with an all-reduce across the four chips."""
    from jax.sharding import PartitionSpec as P

    from repro.compat import make_mesh, shard_map
    from repro.fl.aggregation import shard_weighted_aggregate
    from repro.kernels.fedavg_agg import ops

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    mesh = make_mesh((4,), ("data",), devices=topo.devices)
    step = jax.jit(shard_map(
        lambda x, w: shard_weighted_aggregate({"w": x}, w)["w"],
        mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P()))
    compiled = step.lower(jax.ShapeDtypeStruct((64, 4096), jnp.float32),
                          jax.ShapeDtypeStruct((64,), jnp.float32)).compile()
    text = compiled.as_text()
    assert KERNEL_MARKER in text and "all-reduce" in text
