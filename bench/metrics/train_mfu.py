"""Model FLOP utilisation of the whole federated round.

Operations of the real (unmasked) training samples in the traced window,
three forward passes each, over the window times the chips times the
chip's bf16 peak.  Padding, evaluation and the merge are not counted.
"""
from bench.harness import flops

LAYER = "whole round"
UNIT = "%"
MOVES = "round_s"


def read(r):
    if r.kind != "train" or not r.counts.get("real_elements"):
        return None
    work = r.counts["real_elements"] * flops.train_flops(r.config)
    return 100.0 * work / (r.window_s * r.chips
                           * r.peaks["bf16_flops_per_s"])
