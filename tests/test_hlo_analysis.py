"""Unit tests for the loop-aware HLO cost analyzer (launch/hlo_analysis).

The analyzer underpins every §Roofline number, so its two key properties
are pinned here: (1) `while` bodies are multiplied by their trip count
(XLA's own cost_analysis counts them once); (2) collective bytes are
extracted per kind (checked in a multi-device subprocess).
"""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.launch import hlo_analysis as H


def _flops_of(fn, *args):
    txt = jax.jit(fn).lower(*args).compile().as_text()
    return H.analyze(txt), txt


def test_scan_flops_scaled_by_trip_count():
    n, d = 10, 256
    x = jax.ShapeDtypeStruct((d, d), jnp.float32)
    ws = jax.ShapeDtypeStruct((n, d, d), jnp.float32)

    def scanned(x, ws):
        def body(c, w):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, ws)
        return out

    costs, txt = _flops_of(scanned, x, ws)
    expected = n * 2 * d ** 3
    assert abs(costs.flops - expected) / expected < 0.05, costs.flops
    # XLA's own count misses the trip factor (read through the repro.compat
    # normalizer: cost_analysis() is a dict or a list-of-dict by version)
    xla = H.xla_cost(jax.jit(scanned).lower(x, ws).compile())
    assert xla["flops"] < costs.flops / (n / 2)


def test_single_dot_flops_exact():
    d = 128
    x = jax.ShapeDtypeStruct((d, d), jnp.float32)

    def f(x):
        return x @ x

    costs, _ = _flops_of(f, x)
    assert costs.flops == pytest.approx(2 * d ** 3, rel=0.01)


def test_bytes_positive_and_bounded():
    d = 512
    x = jax.ShapeDtypeStruct((d, d), jnp.float32)
    costs, _ = _flops_of(lambda x: jnp.tanh(x @ x), x)
    # at least: read x twice + write result; at most a few round trips
    assert 3 * d * d * 4 <= costs.bytes <= 40 * d * d * 4


COLLECTIVE_TEST = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.compat import make_mesh
    from repro.launch import hlo_analysis as H

    mesh = make_mesh((8,), ("data",))
    sh = NamedSharding(mesh, P(None, "data"))
    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)

    def f(a):
        return jnp.sum(a @ a.T)          # contraction over the sharded dim

    with mesh:
        txt = jax.jit(f, in_shardings=sh).lower(x).compile().as_text()
    costs = H.analyze(txt)
    assert costs.collective_total > 0, costs.collectives
    assert any(k in costs.collectives
               for k in ("all-reduce", "reduce-scatter", "all-gather")), \\
        costs.collectives
    print("COLLECTIVES_OK", costs.collectives)
""")


@pytest.mark.slow
def test_collectives_detected_multidevice():
    r = subprocess.run([sys.executable, "-c", COLLECTIVE_TEST],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "COLLECTIVES_OK" in r.stdout
