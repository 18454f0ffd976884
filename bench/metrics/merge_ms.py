"""Host time per cross-region merge: the benchmark's span around
``SAGINEngine._policy_merge`` (federation plan, staleness-weighted
average, evaluation and install on every recipient; ``sim/engine.py``,
``fl/federation``)."""

LAYER = "engine and federation"
UNIT = "ms"
MOVES = "round_s"


def read(r):
    spans = r.spans.get("merge")
    if r.kind != "train" or not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
