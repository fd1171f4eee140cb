"""Server-side aggregation — FedAvg on the Pallas reduction kernels.

``fedavg``            — weighted average of client pytrees.
``fedavg_quantized``  — aggregates int8 client payloads with fused
                        dequant+reduce (never materialises f32 copies).
``StreamingAccumulator`` — O(model) running fold for the fleet-scale hub
                        (one ``acc += eff * update`` per arrival instead
                        of buffering O(clients) update trees).
``staleness_weight``  — FedBuff-style polynomial discount for async modes.
``merge_global``      — staleness-damped server update (event-driven modes).
Aggregation compute time is measured for the Fig 5 'aggregation' bars,
by the ``repro.obs`` span around each call (``hub.fedavg``, ``hub.fold``,
``hub.merge``).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np

from repro import obs
from repro.kernels import ops


def fedavg(updates: Sequence, weights, *, interpret=None):
    """updates: list of pytrees; weights ~ num_examples per client."""
    with obs.span("hub.fedavg", updates=len(updates)) as sp:
        agg = ops.fedavg_aggregate(updates, weights, interpret=interpret)
        agg = jax.block_until_ready(agg)
    return agg, sp.seconds


def fedavg_quantized(packed_list: Sequence[dict], weights, unflatten, *,
                     interpret=None):
    with obs.span("hub.fedavg", updates=len(packed_list)) as sp:
        agg = ops.fedavg_aggregate_q8(packed_list, weights, unflatten,
                                      interpret=interpret)
        agg = jax.block_until_ready(agg)
    return agg, sp.seconds


class StreamingAccumulator:
    """O(model) streaming replacement for the hub's dense update buffer.

    ``fold`` adds one effective-weight-scaled update into a flat f32
    running sum (``ops.fedavg_accumulate_flat`` — the fedavg_reduce
    streaming-accumulate kernel path); ``merged`` divides by the summed
    effective weight, which equals the dense ``fedavg(trees, eff)``
    normalised average within float tolerance (tested). Virtual payloads
    fold as bookkeeping only (count / weight sums), so paper-scale runs
    keep their analytic merge timing.
    """

    def __init__(self):
        self.acc = None  # flat f32 running sum of eff-weighted updates
        self.unflatten = None
        self.sum_eff = 0.0
        self.sum_weight = 0.0
        self.count = 0  # client updates folded (records' ``count`` sum)
        self.agg_s = 0.0  # accumulated fold compute seconds

    def fold(self, rec, alpha: float, *, interpret=None):
        """rec: scheduler UpdateRecord; alpha: its staleness discount."""
        from repro.core.message import TensorPayload
        eff = rec.weight * float(alpha)
        self.sum_eff += eff
        self.sum_weight += rec.weight
        self.count += rec.count
        if isinstance(rec.payload, TensorPayload):
            update = (f"{rec.client.client_id}/v{rec.version}"
                      if rec.client is not None else None)
            with obs.span("hub.fold", update=update) as sp:
                flat, unflatten = ops.flatten_pytree(rec.payload.tree)
                if self.acc is None:
                    self.unflatten = unflatten
                    self.acc = ops.fedavg_accumulate_flat(
                        np.zeros(flat.shape[0], np.float32), flat, eff,
                        interpret=interpret)
                else:
                    self.acc = ops.fedavg_accumulate_flat(
                        self.acc, flat, eff, interpret=interpret)
                jax.block_until_ready(self.acc)
            self.agg_s += sp.seconds

    def merged(self, **attrs):
        """-> (merged pytree | None, measured agg seconds). ``attrs`` go
        on the ``hub.merge`` span beside the updates and weight merged."""
        if self.acc is None or self.sum_eff <= 0:
            return None, self.agg_s
        with obs.span("hub.merge", updates=self.count, weight=self.sum_eff,
                      **attrs) as sp:
            tree = self.unflatten(self.acc / np.float32(self.sum_eff))
            tree = jax.block_until_ready(tree)
        return tree, self.agg_s + sp.seconds

    def reset(self):
        self.acc = None
        self.unflatten = None
        self.sum_eff = 0.0
        self.sum_weight = 0.0
        self.count = 0
        self.agg_s = 0.0


def simulated_agg_time(nbytes: int, n_clients: int,
                       hbm_bw: float = 400e9) -> float:
    """Aggregation is bandwidth-bound: read N updates + write one
    (used when payloads are virtual)."""
    return (n_clients + 1) * nbytes / hbm_bw


def staleness_weight(staleness: float, exponent: float = 0.5) -> float:
    """FedBuff-style polynomial staleness discount ``(1 + s)^-a``.

    ``s`` is how many global versions elapsed between the model a client
    trained on and the one it is merged into; ``a = 0`` disables the
    discount (every update counts fully, the sync-FedAvg limit)."""
    return (1.0 + max(float(staleness), 0.0)) ** (-exponent)


def merge_global(global_tree, merged_tree, lam: float):
    """Damped server update: ``(1 - lam) * global + lam * merged``.

    ``lam = server_lr * (effective weight / raw weight)`` — a buffer of
    fresh updates (lam -> 1) replaces the global model exactly like sync
    FedAvg; a stale-heavy buffer moves it proportionally less."""
    lam = min(max(lam, 0.0), 1.0)
    if global_tree is None or lam >= 1.0 - 1e-12:
        return merged_tree
    return jax.tree.map(lambda g, m: (1.0 - lam) * g + lam * m,
                        global_tree, merged_tree)
