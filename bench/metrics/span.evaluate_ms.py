"""Host time of a region's evaluation per region round, from the program's
own phase ``repro.region.evaluate`` (``fl/client.evaluate`` and the read
of its accuracy; it waits for the eq.-(13) aggregate too), timed by an
enabled tracer over rounds that run without the profiler."""
from bench.harness import phases

LAYER = "evaluation"
UNIT = "ms"
MOVES = "round_s"


def read(r):
    return phases.phase_ms(r, "region.evaluate")
