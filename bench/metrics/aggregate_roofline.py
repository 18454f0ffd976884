"""Roofline share of the eq.-(13) aggregate (``fl/aggregation.py``,
``kernels/fedavg_agg``) on one chip.

The stacked client models read once and the average written once, over
the HBM bandwidth, against the device time of the whole aggregate program
(concatenation, padding and kernel are one unit, so it reads the same
work whatever implements it).  On several chips the aggregate runs inside
the sharded local-update program and this reader finds nothing.
"""
from bench.harness import flops

LAYER = "eq.-(13) aggregate"
UNIT = "%"
MOVES = "round_s"
PROGRAMS = (r"_fedavg_multi_impl",)


def read(r):
    if r.kind != "train" or r.chips != 1 or not r.layouts:
        return None
    seconds = r.trace.module_s(PROGRAMS)
    if seconds <= 0:
        return None
    byts = sum(flops.aggregate_bytes(r.config, sum(c for c, _, _ in layout))
               for layout in r.layouts)
    return 100.0 * byts / r.peaks["hbm_bytes_per_s"] / seconds
