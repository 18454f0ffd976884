"""Host array megabytes (10^6 bytes) the program hands to the device per
region round in the profiled window: the cohort engines' own counter
``CohortEngineStats.h2d_bytes`` (every bucket's samples, labels and mask,
and the eq.-(13) weights)."""

LAYER = "cohort staging"
UNIT = "MB"
MOVES = "round_s"


def read(r):
    if r.kind != "train" or not r.counts.get("h2d_bytes"):
        return None
    return r.counts["h2d_bytes"] / r.counts["region_rounds"] / 1e6
