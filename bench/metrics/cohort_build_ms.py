"""Host time of the cohort build per region round: the benchmark's span
around ``CohortEngine.build`` (batch draw, bucket plan and padded host
tensors; ``data/pipeline.py``)."""

LAYER = "cohort build"
UNIT = "ms"
MOVES = "round_s"


def read(r):
    spans = r.spans.get("cohort_build")
    if r.kind != "train" or not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
