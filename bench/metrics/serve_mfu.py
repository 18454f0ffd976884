"""Model FLOP utilisation of the serving window: one forward pass per
request served in the traced window, over the window times the chips
times the chip's bf16 peak.  Padding slots are not counted."""
from bench.harness import flops

LAYER = "whole serve window"
UNIT = "%"
MOVES = "serve_requests_per_s"


def read(r):
    if r.kind != "serve" or not r.counts.get("served"):
        return None
    work = r.counts["served"] * flops.forward_flops(r.config)
    return 100.0 * work / (r.window_s * r.chips
                           * r.peaks["bf16_flops_per_s"])
