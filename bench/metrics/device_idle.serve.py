"""Share of the traced serving window in which no op ran on the device."""

LAYER = "device"
UNIT = "%"
MOVES = "serve_requests_per_s"


def read(r):
    if r.kind != "serve":
        return None
    return 100.0 * (1.0 - r.trace.busy_s() / r.window_s)
