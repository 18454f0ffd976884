"""Operations and bytes of the conv-net programs, from a config's layers.

A multiply-add counts two operations.  Convolutions are 3x3, stride 1,
SAME padding; pooling, ReLU, bias and the loss are not counted.  Training
a sample costs three forward passes (forward, and the backward pass's two
products per layer).
"""
from __future__ import annotations

import math

FLOAT32 = 4


def forward_flops(config) -> int:
    """Operations of one sample's forward pass."""
    h, w, c = config["input_shape"]
    total = 0
    for layer in config["layers"]:
        kind = layer[0]
        if kind == "conv":
            total += 2 * h * w * 9 * c * layer[1]
            c = layer[1]
        elif kind == "pool":
            h, w = h // 2, w // 2
        elif kind == "dense":
            total += 2 * h * w * c * layer[1]
            h, w, c = 1, 1, layer[1]
    return total


def train_flops(config) -> int:
    """Operations of one sample's SGD step: three forward passes."""
    return 3 * forward_flops(config)


def param_count(config) -> int:
    h, w, c = config["input_shape"]
    total = 0
    for layer in config["layers"]:
        kind = layer[0]
        if kind == "conv":
            total += 9 * c * layer[1] + layer[1]
            c = layer[1]
        elif kind == "pool":
            h, w = h // 2, w // 2
        elif kind == "dense":
            total += h * w * c * layer[1] + layer[1]
            h, w, c = 1, 1, layer[1]
    return total


def sample_bytes(config) -> int:
    return FLOAT32 * math.prod(config["input_shape"])


def local_update_cost(config, layout) -> tuple:
    """(operations, bytes) of the local-update programs over a bucket
    layout ``[(clients, steps, batch), ...]``.  The bytes are the least
    any implementation moves: each client's model read and written once
    per SGD step, and every input sample read once."""
    flops = byts = 0
    model = FLOAT32 * param_count(config)
    for c, h, b in layout:
        flops += c * h * b * train_flops(config)
        byts += c * h * (2 * model + b * sample_bytes(config))
    return flops, byts


def aggregate_bytes(config, clients: int) -> int:
    """Bytes of one eq.-(13) aggregate over ``clients`` stacked models:
    the stack read once and the average written once."""
    return FLOAT32 * param_count(config) * (clients + 1)
