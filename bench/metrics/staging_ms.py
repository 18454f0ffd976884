"""Host-to-device staging per region round: the program's wait for a
round's results (``repro.cohort.wait``, the first device read, timed by an
enabled tracer over rounds that run without the profiler), less the device
time of the local-update and aggregate programs that ran inside that wait
in the profiled window, per region round.  What is left is the wait for
the cohort's host-to-device copy.  The profiler inflates the host side of
the copy, not the device's ops, so each side is read where it is not
inflated."""
from bench.harness import phases

LAYER = "cohort staging"
UNIT = "ms"
MOVES = "round_s"


def read(r):
    wait_ms = phases.phase_ms(r, "cohort.wait")
    program = phases.program_of(r)
    rounds = r.counts.get("region_rounds")
    if wait_ms is None or not program or not rounds:
        return None
    waits = [(s, e) for name, s, e in program
             if name == phases.PREFIX + "cohort.wait"]
    device = phases.device_s_within(r.trace, phases.ROUND_PROGRAMS, waits)
    return wait_ms - 1e3 * device / rounds
