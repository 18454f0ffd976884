"""Share of the traced training window in which no op ran on the device
(the mean over the chips in use)."""

LAYER = "device"
UNIT = "%"
MOVES = "round_s"


def read(r):
    if r.kind != "train":
        return None
    return 100.0 * (1.0 - r.trace.busy_s() / r.window_s)
