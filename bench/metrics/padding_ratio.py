"""Padded layout over real batch elements in the traced window, from the
cohort engines' own counters (``CohortEngineStats``): the factor by which
the bucket layout's work exceeds the samples drawn."""

LAYER = "cohort build"
UNIT = "x"
MOVES = "round_s"


def read(r):
    if r.kind != "train" or not r.counts.get("real_elements"):
        return None
    return r.counts["layout_elements"] / r.counts["real_elements"]
