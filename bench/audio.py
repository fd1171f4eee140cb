"""The cross-pod cell's data, drawn by the benchmark from the seed by the
traffic's and the configuration's rules, and handed to the program and to
the reference alike.

Each utterance is a run of segments of 1-8 frames, each segment one
cluster id drawn uniformly; a frame's samples are its cluster's template
(two sinusoids of drawn frequency, amplitude and phase over one frame's
hop of samples) plus Gaussian noise of std 0.1, and the waveform is
normalised to zero mean and unit variance. The span mask is wav2vec 2.0's
(the configuration's ``assumed.mask``): ``int(mask_prob * frames + u)``
distinct starts per utterance (at least 2; u uniform in [0, 1)) among the
first ``frames - mask_length`` frames, ``mask_length`` frames from each.
"""
from __future__ import annotations

import math

import numpy as np

RATE = 16_000  # samples a second


def span_mask(rng, frames: int, mask_prob: float, length: int) -> np.ndarray:
    """(frames,) bool for one utterance."""
    n = min(max(2, int(mask_prob * frames + rng.random())), frames - length)
    mask = np.zeros(frames, bool)
    for s in rng.choice(frames - length, n, replace=False):
        mask[s:s + length] = True
    return mask


def utterance(rng, templates, samples: int, frames: int, config: dict):
    """-> (waveform (samples,) float32, mask (frames,), targets (frames,))."""
    hop = templates.shape[1]
    ends = np.cumsum(rng.integers(1, 9, size=frames))
    ids = rng.integers(0, len(templates), size=frames)
    targets = ids[np.searchsorted(ends, np.arange(frames), side="right")]
    blocks = -(-samples // hop)
    wave = templates[targets[np.minimum(np.arange(blocks), frames - 1)]]
    wave = wave.reshape(-1)[:samples] + 0.1 * rng.standard_normal(samples)
    wave = (wave - wave.mean()) / (wave.std() + 1e-5)
    return (wave.astype(np.float32),
            span_mask(rng, frames, config["mask_prob"], config["mask_length"]),
            targets.astype(np.int32))


def round_batches(config: dict, traffic: dict, seed: int, r: int,
                  frames: int) -> dict:
    """Round ``r``'s batches: {"waveform", "mask", "targets"}, each leaf
    shaped (pods, local steps, pod batch, ...)."""
    hop = math.prod(config["conv_strides"])
    rng = np.random.default_rng([seed, 0])
    shape = (config["num_clusters"], 2, 1)
    freq, amp = rng.uniform(60.0, 4000.0, shape), rng.uniform(0.3, 1.0, shape)
    phase = rng.uniform(0.0, 2 * np.pi, shape)
    templates = np.sum(amp * np.sin(2 * np.pi * freq * np.arange(hop) / RATE
                                    + phase), axis=1)
    rng = np.random.default_rng([seed, 1, r])
    lead = (traffic["pods"], traffic["local_steps"], traffic["pod_batch"])
    rows = [utterance(rng, templates, traffic["samples"], frames, config)
            for _ in range(math.prod(lead))]
    return {name: np.stack([row[i] for row in rows]).reshape(
        lead + rows[0][i].shape)
        for i, name in enumerate(("waveform", "mask", "targets"))}
