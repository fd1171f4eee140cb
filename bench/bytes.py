"""Bytes each kernel of the update path must move, from its shapes.

The least traffic a call needs: every input read once and every output
written once, in the dtypes the kernel uses. Padding counts, since the
kernel reads and writes it.
"""
from __future__ import annotations

F32 = 4
INT8 = 1
COL_TILE = 1024  # fedavg kernels pad the flat length to this
ROW_TILE = 8  # quantize kernels pad rows to this


def _pad(n: int, m: int) -> int:
    return -(-n // m) * m


def fedavg_reduce(n_updates: int, length: int) -> int:
    """Weighted sum of ``n_updates`` flat f32 updates: reads them and the
    weights, writes one."""
    t = _pad(length, COL_TILE)
    return (n_updates * t + n_updates + t) * F32


def fedavg_accumulate(length: int) -> int:
    """``acc + w * x``: reads acc and x, writes acc."""
    return 3 * _pad(length, COL_TILE) * F32


def quantize_rows(lengths, block: int) -> int:
    """Blockwise int8 quantisation of flat f32 vectors, each padded to
    whole (ROW_TILE, block) tiles: reads f32, writes int8 codes and one
    f32 scale per block."""
    rows = sum(_pad(n, block * ROW_TILE) // block for n in lengths)
    return rows * block * (F32 + INT8) + rows * F32


def dequantize_rows(rows: int, block: int) -> int:
    """Reads int8 codes and scales, writes f32."""
    return rows * block * (INT8 + F32) + rows * F32
