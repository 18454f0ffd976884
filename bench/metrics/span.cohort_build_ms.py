"""Host time of the cohort build per region round, from the program's own
phase ``repro.cohort.build`` (``CohortEngine.build``: batch draw, bucket
plan and padded host tensors; ``data/pipeline.py``), timed by an enabled
tracer over rounds that run without the profiler."""
from bench.harness import phases

LAYER = "cohort build"
UNIT = "ms"
MOVES = "round_s"


def read(r):
    return phases.phase_ms(r, "cohort.build")
