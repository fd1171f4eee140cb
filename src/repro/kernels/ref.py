"""Pure-jnp oracles for every Pallas kernel (tests assert_allclose vs these)
plus the pure-NumPy legacy codec (the pre-batching per-message baseline the
perf trajectory and the bit-exactness parity tests compare against)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def quantize_blocks_ref(x):
    """x: (rows, block) -> (q int8, scales f32 (rows,1))."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = amax / 127.0
    inv = jnp.where(scale > 0.0, 1.0 / scale, 0.0)
    q = jnp.clip(jnp.round(x * inv), -127.0, 127.0).astype(jnp.int8)
    return q, scale


def dequantize_blocks_ref(q, scales, out_dtype=jnp.float32):
    return (q.astype(jnp.float32) * scales).astype(out_dtype)


def quantize_blocks_np(x):
    """Pure-NumPy twin of ``quantize_blocks_ref`` (single-threaded, no
    XLA): the legacy per-message codec baseline. Same math, same f32
    rounding (np.round is round-half-even like jnp.round), so its int8
    output is bit-identical to the kernel's."""
    x = np.asarray(x, np.float32)
    amax = np.max(np.abs(x), axis=-1, keepdims=True)
    scale = amax / np.float32(127.0)
    inv = np.divide(np.float32(1.0), scale, where=scale > 0.0,
                    out=np.zeros_like(scale))
    q = np.clip(np.round(x * inv), -127.0, 127.0).astype(np.int8)
    return q, scale


def dequantize_blocks_np(q, scales, out_dtype=np.float32):
    return (np.asarray(q, np.float32) * np.asarray(scales,
                                                   np.float32)).astype(out_dtype)


def fedavg_reduce_ref(updates, weights):
    """updates (N, T), weights (N,) -> (T,) f32."""
    return jnp.sum(updates.astype(jnp.float32)
                   * weights.astype(jnp.float32)[:, None], axis=0)


def fedavg_accumulate_ref(acc, x, w):
    """acc, x (T,), w scalar -> (T,) f32 ``acc + w * x``."""
    return acc.astype(jnp.float32) + jnp.float32(w) * x.astype(jnp.float32)


def topk_rows_ref(x, k: int):
    """x: (B, T) -> (idx (B, k) i32, vals (B, k) f32): the k largest-|.|
    entries per row, |value|-descending, ties broken toward the lower
    index (jax.lax.top_k's order — and the per-message codec's). There is
    no top-k kernel: ``ops.topk_flat_batch`` runs this on every backend."""
    vals_abs, idx = jax.lax.top_k(jnp.abs(x.astype(jnp.float32)), k)
    del vals_abs
    vals = jnp.take_along_axis(x.astype(jnp.float32), idx, axis=-1)
    return idx.astype(jnp.int32), vals


def fedavg_reduce_q8_ref(q, scales, weights, block: int = 256):
    n, t = q.shape
    x = q.astype(jnp.float32).reshape(n, t // block, block) \
        * scales.astype(jnp.float32)[..., None]
    return jnp.sum(x.reshape(n, t) * weights.astype(jnp.float32)[:, None],
                   axis=0)
