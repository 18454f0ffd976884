"""Share of the traced serving window spent outside inference: admission,
routing, batching and accounting on the host (``serve/gateway.py``,
``router.py``, ``workload.py``), from the gateway's ``wall_infer``."""

LAYER = "gateway"
UNIT = "%"
MOVES = "serve_requests_per_s"


def read(r):
    if r.kind != "serve" or not r.counts.get("batches"):
        return None
    return 100.0 * (1.0 - r.counts["wall_infer"] / r.window_s)
