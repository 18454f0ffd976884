"""Host time per cross-region merge, from the program's own phase
``repro.engine.merge`` (``SAGINEngine._policy_merge``: federation plan,
staleness-weighted average, evaluation and install on every recipient),
timed by an enabled tracer over rounds that run without the profiler."""
from bench.harness import phases

LAYER = "engine and federation"
UNIT = "ms"
MOVES = "round_s"


def read(r):
    return phases.phase_ms(r, "engine.merge")
