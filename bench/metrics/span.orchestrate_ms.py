"""Host time of the control plane per region round, from the program's own
phase ``repro.region.orchestrate`` (``SAGINOrchestrator.step``: offloading
optimiser, handover plan, network dynamics; ``core/``, ``sim/dynamics.py``),
timed by an enabled tracer over rounds that run without the profiler."""
from bench.harness import phases

LAYER = "control plane"
UNIT = "ms"
MOVES = "round_s"


def read(r):
    return phases.phase_ms(r, "region.orchestrate")
