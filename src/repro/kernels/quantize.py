"""Pallas TPU kernel: blockwise symmetric int8 quantisation.

This is the compression hot-spot of the communication layer (QSGD-style
int8 payloads for cross-pod/cross-silo sync, §Compression in DESIGN.md).
Layout: input viewed as (rows, block) — one scale per row-block of
``block`` contiguous elements. Tiles are (ROW_TILE, block) in VMEM; the
lane dimension equals the quant block so the reduction is a single in-tile
max (MXU-free, pure VPU work).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROW_TILE = 8  # f32 sublane tile


def _quantize_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)  # (rows, 1)
    scale = amax / 127.0
    inv = jnp.where(scale > 0.0, 1.0 / scale, 0.0)
    q = jnp.clip(jnp.round(x * inv), -127.0, 127.0)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _dequantize_kernel(q_ref, s_ref, x_ref, *, out_dtype):
    q = q_ref[...].astype(jnp.float32)
    x_ref[...] = (q * s_ref[...]).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_blocks(x, *, interpret: bool = False):
    """x: (rows, block) float -> (q int8 (rows, block), scales f32 (rows, 1)).

    rows must be a multiple of ROW_TILE (ops.py pads).
    """
    rows, block = x.shape
    assert rows % ROW_TILE == 0, rows
    grid = (rows // ROW_TILE,)
    return pl.pallas_call(
        _quantize_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((ROW_TILE, block), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((ROW_TILE, block), lambda i: (i, 0)),
                   pl.BlockSpec((ROW_TILE, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, block), jnp.int8),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32)],
        interpret=interpret,
    )(x)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def dequantize_blocks(q, scales, *, out_dtype=jnp.float32,
                      interpret: bool = False):
    rows, block = q.shape
    assert rows % ROW_TILE == 0, rows
    grid = (rows // ROW_TILE,)
    return pl.pallas_call(
        functools.partial(_dequantize_kernel, out_dtype=out_dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((ROW_TILE, block), lambda i: (i, 0)),
                  pl.BlockSpec((ROW_TILE, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((ROW_TILE, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, block), out_dtype),
        interpret=interpret,
    )(q, scales)
