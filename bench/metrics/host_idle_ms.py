"""Milliseconds per region round, in the profiled window, in which the
device sat idle while the innermost open program phase was host work:
``region.orchestrate``, ``cohort.build``, ``region.evaluate``,
``engine.merge``, or ``region.step`` itself (the step's own bookkeeping
between its phases).  Idle time under ``cohort.dispatch`` and
``cohort.wait`` belongs to the copy, and is not counted."""
from bench.harness import phases

LAYER = "engine (host phases)"
UNIT = "ms"
MOVES = "round_s"
HOST = ("region.orchestrate", "cohort.build", "region.evaluate",
        "engine.merge", "region.step")


def read(r):
    program = phases.program_of(r)
    rounds = r.counts.get("region_rounds")
    if r.kind != "train" or not program or not rounds:
        return None
    idle = phases.idle_by_phase(r.trace, program)
    return 1e3 * sum(idle.get(p, 0.0) for p in HOST) / rounds
