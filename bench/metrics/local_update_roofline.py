"""Roofline share of the bucketed local update (``fl/client.py`` through
``fl/cohort_engine.py``).

The least time the chip could take for the bucket layouts the traced
window ran (the larger of their operations over the bf16 peak and their
least bytes over the HBM bandwidth; ``bench/harness/flops.py``), over the
device time of the programs that run them.  On several chips the work is
shared, so the least time per chip is divided by their number.
"""
from bench.harness import flops

LAYER = "local update"
UNIT = "%"
MOVES = "round_s"
#: the jitted programs of the local update, as the trace names them
PROGRAMS = (r"cohort_local_update", r"_cohort_round_impl", r"bucket_step")


def read(r):
    if r.kind != "train" or not r.layouts:
        return None
    seconds = r.trace.module_s(PROGRAMS)
    if seconds <= 0:
        return None
    ops = byts = 0
    for layout in r.layouts:
        f, b = flops.local_update_cost(r.config, layout)
        ops += f
        byts += b
    least = max(ops / r.peaks["bf16_flops_per_s"],
                byts / r.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (r.chips * seconds)
