"""Record the small chip trace that ``test_trace.py`` reads.

    python bench/tests/make_trace.py <out directory>

On a TPU: inside ``bench.window``, two dispatches of a jitted matmul
program (``jit_bench_matmul``), then 50 ms in which the host sleeps under
the annotation ``bench.host_sleep`` and the device has nothing to do, then
one more dispatch.  Copy the ``.xplane.pb`` it writes to
``bench/tests/data/small.xplane.pb``.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

SLEEP_S = 0.05


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp

    from bench.harness import core
    from bench.harness.trace import capture

    argv = sys.argv[1:] if argv is None else argv
    core.require_tpu(1)

    def bench_matmul(a):
        return jnp.tanh(a @ a) @ a

    f = jax.jit(bench_matmul)
    a = jnp.ones((1024, 1024), jnp.float32) * 1e-3
    f(a).block_until_ready()
    with capture(argv[0]):
        f(a).block_until_ready()
        f(a).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.host_sleep"):
            time.sleep(SLEEP_S)
        f(a).block_until_ready()
    return 0


if __name__ == "__main__":
    sys.exit(main())
