"""Analytic operations of HuBERT, from the configuration's shapes.

Counted: the multiply-adds of the CNN's convolutions, the feature
projection, the positional convolution, each layer's projections and MLP,
attention's scores and weighted values over every frame, the head's
projection and its logits against every cluster code, two operations
each. Normalisation, activations, softmax and the cosine's norms are left
out, as model FLOP counts usually do. A training step costs three forward
passes (forward, and backward to activations and to weights);
rematerialisation is not counted.
"""
from __future__ import annotations


def forward(cfg, samples: int) -> int:
    """One utterance of ``samples`` samples."""
    n, c_in, f = samples, 1, 0
    c = cfg["conv_channels"]
    for k, s in zip(cfg["conv_kernels"], cfg["conv_strides"]):
        n = (n - k) // s + 1
        f += 2 * n * k * c_in * c
        c_in = c
    t, d = n, cfg["d_model"]
    f += 2 * t * c * d
    f += 2 * t * cfg["pos_conv_kernel"] * (d // cfg["pos_conv_groups"]) * d
    layer = 2 * t * (4 * d * d + 2 * d * cfg["d_ff"]) + 2 * 2 * t * t * d
    f += cfg["num_layers"] * layer
    f += 2 * t * d * cfg["final_dim"]
    f += 2 * t * cfg["final_dim"] * cfg["num_clusters"]
    return f


def train_step(cfg, batch: int, samples: int) -> int:
    """Forward and backward of one pod's local step over ``batch``
    utterances."""
    return 3 * batch * forward(cfg, samples)
