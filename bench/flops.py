"""Analytic operations of the image tiers, from the configuration's shapes.

Counted: the multiply-adds of convolutions and dense layers, two
operations each; normalisation, activations and pooling are left out, as
model FLOP counts usually do. A training step costs three forward passes
(forward, and backward to activations and to weights).
"""
from __future__ import annotations

import math


def _conv(h, w, k, c_in, c_out, groups=1):
    return 2 * h * w * k * k * (c_in // groups) * c_out


def _out(size, stride):
    return math.ceil(size / stride)


def resnet_forward(cfg) -> int:
    s = cfg["image_size"]
    w0 = cfg["widths"][0]
    f = _conv(s, s, 3, 3, w0)
    c_in = w0
    for si, width in enumerate(cfg["widths"]):
        for bi in range(cfg["blocks_per_stage"]):
            stride = 2 if (si > 0 and bi == 0) else 1
            s = _out(s, stride)
            f += _conv(s, s, 3, c_in, width) + _conv(s, s, 3, width, width)
            if stride != 1 or c_in != width:
                f += _conv(s, s, 1, c_in, width)
            c_in = width
    return f + 2 * c_in * cfg["num_classes"]


FORWARD = {"resnet": resnet_forward}


def train_step(cfg, batch: int) -> int:
    """Forward and backward of one local step over ``batch`` images."""
    return 3 * batch * FORWARD[cfg["model"]](cfg)
