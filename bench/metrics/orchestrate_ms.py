"""Host time of the control plane per region round: the benchmark's span
around ``SAGINOrchestrator.step`` (offloading optimiser, handover plan,
network dynamics; ``core/``, ``sim/dynamics.py``)."""

LAYER = "control plane"
UNIT = "ms"
MOVES = "round_s"


def read(r):
    spans = r.spans.get("orchestrate")
    if r.kind != "train" or not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
