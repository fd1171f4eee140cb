"""Top-k magnitude sparsification with error feedback (Wangni et al. 2018).

The selection runs through kernels/ops ``topk_flat_batch``: messages
sharing a (length, k) land in one stacked ``jax.lax.top_k`` dispatch, and
the sparse wire form (|value|-descending, ties to the lower index) is
bit-identical to the per-message ``top_k(|flat|)`` + gather.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.compression.qsgd import QuantState
from repro.kernels import ops


def topk_compress(tree, k_frac: float, state: Optional[QuantState] = None):
    """-> (payload dict {idx, vals, n}, new_state, unflatten)."""
    flat, unflatten = ops.flatten_pytree(tree)
    (payload,), (new_state,) = topk_compress_flat_batch(
        [flat], [state], k_frac=k_frac)
    return payload, new_state, unflatten


def topk_compress_flat_batch(flats, states, *, k_frac: float):
    """Batched core: [flat_i], [state_i|None] -> ([payload_i],
    [new_state_i]). Same-shape messages share one fused top-k dispatch;
    per-item payloads and error-feedback transitions are bit-identical
    to ``topk_compress`` run message by message."""
    fed = [f if s is None else f + s.error for f, s in zip(flats, states)]
    payloads = ops.topk_flat_batch(fed, k_frac=k_frac)
    new_states = [None] * len(flats)
    for i, s in enumerate(states):
        if s is None:
            continue
        recon = np.zeros(int(payloads[i]["n"]), np.float32)
        recon[np.asarray(payloads[i]["idx"])] = np.asarray(
            payloads[i]["vals"])
        new_states[i] = QuantState(error=jnp.asarray(fed[i]) - recon)
    return payloads, new_states


def topk_decompress(payload, unflatten):
    flat = jnp.zeros((int(payload["n"]),), jnp.float32)
    flat = flat.at[jnp.asarray(payload["idx"])].set(
        jnp.asarray(payload["vals"]))
    return unflatten(flat)


def payload_nbytes(payload) -> int:
    return int(payload["idx"].size) * 4 + int(payload["vals"].size) * 4
