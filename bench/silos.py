"""Per-silo image datasets for the live FL cells, made from the run's seed.

A copy of the program's silo generator (``repro.data.synthetic``): each
silo draws its labels from a Dirichlet(alpha) skew over the classes, and
its images are a class-dependent low-frequency pattern plus noise, so
local training has something to learn. The benchmark owns this copy, so
a change to the program's generator cannot change what is measured.

Batches are drawn from ``(run seed, silo, draw seed)``: the program picks
the draw seed (its data order), the benchmark the data. Every draw is
logged, so the reference replays exactly the batches the program trained
on.
"""
from __future__ import annotations

import numpy as np


class SiloDataset:
    """One silo's shard, with the interface ``FLClient`` reads."""

    def __init__(self, silo_id: int, images: np.ndarray, labels: np.ndarray,
                 num_classes: int, run_seed: int):
        self.silo_id = silo_id
        self.kind = "image"
        self.features = images
        self.labels = labels
        self.num_classes = num_classes
        self.run_seed = run_seed
        self.draws: list = []  # (batch_size, draw seed), in call order

    def num_examples(self) -> int:
        return len(self.labels)

    def batch_indices(self, batch_size: int, seed: int, n_batches: int):
        """The example indices of the first ``n_batches`` batches that
        ``batches(batch_size, seed)`` yields."""
        rng = np.random.default_rng([self.run_seed, self.silo_id, seed])
        n = self.num_examples()
        return [rng.choice(n, size=min(batch_size, n), replace=False)
                for _ in range(n_batches)]

    def batches(self, batch_size: int, seed: int = 0):
        self.draws.append((batch_size, seed))
        rng = np.random.default_rng([self.run_seed, self.silo_id, seed])
        n = self.num_examples()
        while True:
            idx = rng.choice(n, size=min(batch_size, n), replace=False)
            yield {"images": self.features[idx], "labels": self.labels[idx]}


def make_silos(num_silos: int, *, examples_per_silo: int, num_classes: int,
               image_size: int, alpha: float, seed: int):
    """Dirichlet(alpha) label skew across silos; class-dependent patterns."""
    rng = np.random.default_rng(seed)
    proportions = rng.dirichlet([alpha] * num_classes, size=num_silos)
    xs = np.linspace(0, np.pi * 2, image_size, dtype=np.float32)
    grid = np.stack([np.sin(np.outer(xs * (k % 4 + 1), xs))
                     for k in range(num_classes)]).astype(np.float32)
    silos = []
    for sid in range(num_silos):
        labels = rng.choice(num_classes, size=examples_per_silo,
                            p=proportions[sid]).astype(np.int32)
        base = rng.normal(size=(examples_per_silo, image_size, image_size, 3)
                          ).astype(np.float32) * 0.3
        images = base + grid[labels][..., None]
        silos.append(SiloDataset(sid, images, labels, num_classes, seed))
    return silos
