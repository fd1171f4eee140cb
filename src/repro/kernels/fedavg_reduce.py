"""Pallas TPU kernel: fused weighted aggregation of N client updates.

The FL server's compute hot-spot: ``agg = sum_i w_i * update_i`` over N
stacked flat updates. Two variants:

* ``fedavg_reduce``   — float inputs (N, T).
* ``fedavg_reduce_q8`` — int8 inputs + per-(client, block) scales, fusing
  dequantisation into the reduction so the dequantised f32 copies are never
  materialised in HBM (N x T x 4 bytes saved vs dequant-then-sum).
* ``fedavg_accumulate`` — the streaming form: fold ONE weighted update
  into a running accumulator, ``acc + w * x``. The fleet-scale hub calls
  this once per arriving update, so server memory is O(model) instead of
  the O(clients x model) stacked buffer the batch reduction needs.

Tiling: grid over T in COL_TILE lanes; each step holds an (N, COL_TILE)
tile in VMEM (N <= ~64 clients keeps tiles < 1 MB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

COL_TILE = 1024


def _fedavg_kernel(x_ref, w_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)  # (N, C)
    w = w_ref[...].astype(jnp.float32)  # (N, 1)
    o_ref[...] = jnp.sum(x * w, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fedavg_reduce(updates, weights, *, interpret: bool = False):
    """updates: (N, T) float; weights: (N,) -> (T,) f32 weighted sum.
    T must be a multiple of COL_TILE (ops.py pads)."""
    n, t = updates.shape
    assert t % COL_TILE == 0, t
    grid = (t // COL_TILE,)
    out = pl.pallas_call(
        _fedavg_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((n, COL_TILE), lambda i: (0, i)),
                  pl.BlockSpec((n, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, COL_TILE), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, t), jnp.float32),
        interpret=interpret,
    )(updates, weights.reshape(n, 1))
    return out[0]


def _accum_kernel(a_ref, x_ref, w_ref, o_ref):
    a = a_ref[...].astype(jnp.float32)  # (1, C)
    x = x_ref[...].astype(jnp.float32)  # (1, C)
    w = w_ref[...].astype(jnp.float32)  # (1, 1)
    o_ref[...] = a + w * x


@functools.partial(jax.jit, static_argnames=("interpret",))
def fedavg_accumulate(acc, x, w, *, interpret: bool = False):
    """acc, x: (T,) float; w: scalar -> (T,) f32 ``acc + w * x``.
    T must be a multiple of COL_TILE (ops.py pads)."""
    t = acc.shape[0]
    assert t % COL_TILE == 0, t
    grid = (t // COL_TILE,)
    w = jnp.asarray(w, jnp.float32).reshape(1, 1)
    out = pl.pallas_call(
        _accum_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, COL_TILE), lambda i: (0, i)),
                  pl.BlockSpec((1, COL_TILE), lambda i: (0, i)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, COL_TILE), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, t), jnp.float32),
        interpret=interpret,
    )(acc.reshape(1, t), x.reshape(1, t), w)
    return out[0]


def _fedavg_q8_kernel(q_ref, s_ref, w_ref, o_ref, *, block: int):
    q = q_ref[...].astype(jnp.float32)  # (N, C)
    s = s_ref[...].astype(jnp.float32)  # (N, C // block)
    w = w_ref[...].astype(jnp.float32)  # (N, 1)
    n, c = q.shape
    x = q.reshape(n, c // block, block) * s[..., None]
    o_ref[...] = jnp.sum(x.reshape(n, c) * w, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fedavg_reduce_q8(q, scales, weights, *, block: int = 256,
                     interpret: bool = False):
    """q: (N, T) int8; scales: (N, T // block) f32; weights: (N,).
    Fused dequant + weighted sum -> (T,) f32."""
    n, t = q.shape
    assert t % COL_TILE == 0 and COL_TILE % block == 0
    grid = (t // COL_TILE,)
    sc_per_tile = COL_TILE // block
    out = pl.pallas_call(
        functools.partial(_fedavg_q8_kernel, block=block),
        grid=grid,
        in_specs=[pl.BlockSpec((n, COL_TILE), lambda i: (0, i)),
                  pl.BlockSpec((n, sc_per_tile), lambda i: (0, i)),
                  pl.BlockSpec((n, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, COL_TILE), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, t), jnp.float32),
        interpret=interpret,
    )(q, scales, weights.reshape(n, 1))
    return out[0]
