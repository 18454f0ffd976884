"""Wall time per batched inference of the CNN backend
(``serve/backends.py``): the gateway's own ``wall_infer`` counter, which
includes the sync, over the batches dispatched in the traced window."""

LAYER = "CNN backend"
UNIT = "ms"
MOVES = "serve_requests_per_s"


def read(r):
    if r.kind != "serve" or not r.counts.get("batches"):
        return None
    return 1e3 * r.counts["wall_infer"] / r.counts["batches"]
