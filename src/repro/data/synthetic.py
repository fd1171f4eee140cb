"""Deterministic synthetic data pipeline.

Two flavours:
* LM token streams (markov-ish structure so loss actually decreases) for the
  at-scale archs;
* per-silo non-IID labelled datasets (images or token sequences) for the
  cross-silo FL path — each silo gets a Dirichlet-skewed label distribution,
  the standard FL heterogeneity model.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Iterator, Optional

import numpy as np


def synthetic_lm_batch(rng: np.random.Generator, batch: int, seq: int,
                       vocab: int):
    """Structured token stream: next token = (3*prev + noise) % vocab, which
    a causal model can learn quickly (used to check loss decreases)."""
    t0 = rng.integers(0, vocab, size=(batch, 1))
    toks = [t0]
    for _ in range(seq):
        nxt = (3 * toks[-1] + rng.integers(0, 7, size=(batch, 1))) % vocab
        toks.append(nxt)
    toks = np.concatenate(toks, axis=1)
    return {"tokens": toks[:, :seq].astype(np.int32),
            "targets": toks[:, 1:seq + 1].astype(np.int32)}


def lm_batch_iterator(seed: int, batch: int, seq: int, vocab: int) -> Iterator:
    rng = np.random.default_rng(seed)
    while True:
        yield synthetic_lm_batch(rng, batch, seq, vocab)


# ---------------------------------------------------------------------------
# per-silo FL datasets
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SiloDataset:
    """One silo's local shard."""
    silo_id: int
    kind: str  # image | text
    features: np.ndarray  # images (N,H,W,3) or tokens (N,S)
    labels: np.ndarray  # (N,)
    num_classes: int

    def num_examples(self) -> int:
        return len(self.labels)

    def batches(self, batch_size: int, seed: int = 0) -> Iterator[dict]:
        rng = np.random.default_rng(seed * 1000 + self.silo_id)
        n = self.num_examples()
        while True:
            idx = rng.choice(n, size=min(batch_size, n), replace=False)
            key = "images" if self.kind == "image" else "tokens"
            yield {key: self.features[idx], "labels": self.labels[idx]}


def make_silo_datasets(num_silos: int, *, kind: str = "image",
                       examples_per_silo: int = 128, num_classes: int = 16,
                       image_size: int = 32, seq_len: int = 64,
                       vocab: int = 30522, alpha: float = 0.5,
                       seed: int = 0):
    """Dirichlet(alpha) label skew across silos; class-conditional synthetic
    features so that learning is possible (class-dependent mean patterns)."""
    rng = np.random.default_rng(seed)
    proportions = rng.dirichlet([alpha] * num_classes, size=num_silos)
    class_dirs = rng.normal(size=(num_classes, 8)).astype(np.float32)
    silos = []
    for sid in range(num_silos):
        labels = rng.choice(num_classes, size=examples_per_silo,
                            p=proportions[sid]).astype(np.int32)
        if kind == "image":
            base = rng.normal(
                size=(examples_per_silo, image_size, image_size, 3)
            ).astype(np.float32) * 0.3
            # class-dependent low-frequency pattern
            xs = np.linspace(0, np.pi * 2, image_size, dtype=np.float32)
            grid = np.stack([np.sin(np.outer(xs * (k % 4 + 1), xs))
                             for k in range(num_classes)])
            feats = base + grid[labels][..., None]
            silos.append(SiloDataset(sid, "image", feats, labels, num_classes))
        else:
            toks = rng.integers(0, vocab, size=(examples_per_silo, seq_len))
            # class-dependent token bias in the first positions
            toks[:, :8] = (labels[:, None] * 37 +
                           np.arange(8)[None]) % min(vocab, 1000)
            silos.append(SiloDataset(sid, "text", toks.astype(np.int32),
                                     labels, num_classes))
    return silos


# ---------------------------------------------------------------------------
# audio for masked prediction (HuBERT)
# ---------------------------------------------------------------------------

SAMPLE_RATE = 16_000


def cluster_templates(rng: np.random.Generator, num_clusters: int,
                      hop: int) -> np.ndarray:
    """(num_clusters, hop) float32: one frame of sound per cluster, two
    sinusoids at drawn frequencies, so that a frame's cluster can be heard
    in its samples as k-means targets can be in real speech."""
    freq = rng.uniform(60.0, 4000.0, size=(num_clusters, 2, 1))
    amp = rng.uniform(0.3, 1.0, size=(num_clusters, 2, 1))
    phase = rng.uniform(0.0, 2 * np.pi, size=(num_clusters, 2, 1))
    t = np.arange(hop) / SAMPLE_RATE
    return np.sum(amp * np.sin(2 * np.pi * freq * t + phase),
                  axis=1).astype(np.float32)


def span_mask(rng: np.random.Generator, batch: int, frames: int,
              mask_prob: float, mask_length: int) -> np.ndarray:
    """(batch, frames) bool, wav2vec 2.0's span mask: each row takes
    ``int(mask_prob * frames + u)`` distinct starts (at least 2, u uniform
    in [0, 1)) among its first ``frames - mask_length`` frames and masks
    ``mask_length`` frames from each; spans may overlap."""
    if frames <= mask_length:
        raise ValueError(f"{frames} frames cannot hold a span of "
                         f"{mask_length}")
    mask = np.zeros((batch, frames), bool)
    span = np.arange(mask_length)
    for i in range(batch):
        n = max(2, int(mask_prob * frames + rng.random()))
        n = min(n, frames - mask_length)
        starts = rng.choice(frames - mask_length, n, replace=False)
        mask[i, (starts[:, None] + span).reshape(-1)] = True
    return mask


def audio_batch(rng: np.random.Generator, templates: np.ndarray, batch: int,
                samples: int, frames: int, *, mask_prob: float,
                mask_length: int, noise: float = 0.1) -> dict:
    """Utterances of ``samples`` samples for masked prediction.

    Each utterance is a run of segments of 1-8 frames, each segment one
    cluster id drawn uniformly; a frame's samples are its cluster's
    template (``templates``, one hop each) plus Gaussian noise, and the
    waveform is normalised to zero mean and unit variance, as the recipe's
    dataset normalises audio. -> {"waveform" (batch, samples) float32,
    "mask" (batch, frames) bool, "targets" (batch, frames) int32}."""
    num_clusters, hop = templates.shape
    targets = np.empty((batch, frames), np.int32)
    for i in range(batch):
        seg = np.cumsum(rng.integers(1, 9, size=frames))
        ids = rng.integers(0, num_clusters, size=frames)
        targets[i] = ids[np.searchsorted(seg, np.arange(frames),
                                         side="right")]
    blocks = -(-samples // hop)
    per_block = targets[:, np.minimum(np.arange(blocks), frames - 1)]
    wave = templates[per_block].reshape(batch, blocks * hop)[:, :samples]
    wave = wave + noise * rng.standard_normal((batch, samples),
                                              dtype=np.float32)
    wave -= wave.mean(axis=1, keepdims=True)
    wave /= wave.std(axis=1, keepdims=True) + 1e-5
    return {"waveform": wave.astype(np.float32),
            "mask": span_mask(rng, batch, frames, mask_prob, mask_length),
            "targets": targets}


def audio_stream(cfg, seed: int, batch: int, samples: int) -> Iterator[dict]:
    """Endless ``audio_batch``es for the HuBERT config ``cfg``: one set of
    cluster templates, then one batch per draw, all from ``seed``."""
    from repro.models.hubert import frames_for
    rng = np.random.default_rng(seed)
    templates = cluster_templates(rng, cfg.vocab_size,
                                  math.prod(cfg.conv_strides))
    frames = frames_for(cfg, samples)
    while True:
        yield audio_batch(rng, templates, batch, samples, frames,
                          mask_prob=cfg.mask_prob,
                          mask_length=cfg.mask_length)
