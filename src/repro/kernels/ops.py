"""jit'd public wrappers over the Pallas kernels: pytree-level quantise /
dequantise / aggregate with padding + flattening handled here.

The kernels are written for the TPU. ``interpret=None`` resolves from the
default backend: compiled kernels on a TPU; on the CPU, where tests and
host-only simulator runs happen, the Pallas interpreter or the jitted
reference (see the batched API below). Any other backend raises.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import fedavg_reduce as fr
from repro.kernels import quantize as qz
from repro.kernels import ref as kref


def _default_interpret() -> bool:
    """False on a TPU, True on the CPU; no other backend runs the kernels."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"the Pallas kernels target TPU; backend "
                       f"{backend!r} has no kernel path")


# ---------------------------------------------------------------------------
# flat-array helpers
# ---------------------------------------------------------------------------

def _pad_to(x, multiple):
    n = x.shape[0]
    rem = (-n) % multiple
    if rem:
        x = jnp.concatenate([x, jnp.zeros((rem,) + x.shape[1:], x.dtype)])
    return x, n


def quantize_flat(x, *, block: int = 256, interpret=None):
    """x: (T,) float -> dict(q=(T',) int8, scales, block, orig_len)."""
    interpret = _default_interpret() if interpret is None else interpret
    xp, orig = _pad_to(x.reshape(-1), block * qz.ROW_TILE)
    rows = xp.shape[0] // block
    q, s = qz.quantize_blocks(xp.reshape(rows, block), interpret=interpret)
    return {"q": q.reshape(-1), "scales": s.reshape(-1), "block": block,
            "orig_len": orig}


def dequantize_flat(packed, *, out_dtype=jnp.float32, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    block = packed["block"]
    q = packed["q"].reshape(-1, block)
    s = packed["scales"].reshape(-1, 1)
    x = qz.dequantize_blocks(q, s, out_dtype=out_dtype, interpret=interpret)
    return x.reshape(-1)[: packed["orig_len"]]


def _count_copies(site: str, inputs, h2d: int, d2h: int) -> None:
    """Count one batched call's host<->device crossings in bytes: the
    arrays uploaded (``h2d``) and fetched (``d2h``), plus the ``inputs``
    that arrive on the device and are read to the host."""
    obs.count("copy.d2h_bytes", d2h + sum(
        x.nbytes for x in inputs if isinstance(x, jax.Array)), site=site)
    obs.count("copy.h2d_bytes", h2d, site=site)


# ---------------------------------------------------------------------------
# batched flat-array API (the channel's fused encode path)
# ---------------------------------------------------------------------------
#
# One round's outstanding encodes arrive as a *list* of flat vectors. Each
# is padded independently to a whole number of (ROW_TILE, block) row-tiles
# and the tiles are concatenated into one (rows, block) array, so a single
# kernel dispatch quantises the lot — and, because quantisation is
# row-wise, every row is bit-identical to what the per-message call would
# have produced. Dispatch (``interpret``):
#
# * False, or None on a TPU — the compiled Pallas kernel, fused.
# * None on the CPU — the jitted XLA reference (kernels/ref.py): same f32
#   math, parity-tested bit-exact against the interpret-mode kernel. The
#   interpreter walks the grid in Python, too slow for whole-model runs.
# * True — the Pallas interpreter (the parity tests ask for it).

_jit_quantize_ref = jax.jit(kref.quantize_blocks_ref)
_jit_dequantize_ref = jax.jit(kref.dequantize_blocks_ref)


def _quantize_rows(rows_x, interpret):
    """(rows, block) -> (q, scales) through the fastest bit-exact path."""
    if interpret is True:
        return qz.quantize_blocks(rows_x, interpret=True)
    if interpret is False or not _default_interpret():
        return qz.quantize_blocks(rows_x, interpret=False)
    return _jit_quantize_ref(rows_x)


def _dequantize_rows(q, s, interpret):
    if interpret is True:
        return qz.dequantize_blocks(q, s, interpret=True)
    if interpret is False or not _default_interpret():
        return qz.dequantize_blocks(q, s, interpret=False)
    return _jit_dequantize_ref(q, s)


def quantize_flat_batch(flats: Sequence, *, block: int = 256,
                        interpret=None):
    """[x_i] -> [packed_i], one fused kernel dispatch for the whole batch.

    Per-item results are bit-identical to ``quantize_flat(x_i)`` (padding
    is per-item and row-aligned; quantisation is row-wise)."""
    if not flats:
        return []
    mult = block * qz.ROW_TILE
    # pad + concatenate on the host: per-item jnp pads would cost one
    # dispatch each and dominate the small-message regime this API is
    # for; a single zeros+memcpy feeds one device transfer instead
    arrs = [np.asarray(x, np.float32).reshape(-1) for x in flats]
    pad_lens = [-(-a.size // mult) * mult for a in arrs]
    big = np.zeros(sum(pad_lens), np.float32)
    off = 0
    for a, pl in zip(arrs, pad_lens):
        big[off:off + a.size] = a
        off += pl
    q, s = _quantize_rows(jnp.asarray(big.reshape(-1, block)), interpret)
    q, s = np.asarray(q), np.asarray(s)  # one transfer; slices are views
    if obs.recording():
        _count_copies("quantize", flats, big.nbytes, q.nbytes + s.nbytes)
    out, row = [], 0
    for a, pl in zip(arrs, pad_lens):
        rows = pl // block
        out.append({"q": q[row:row + rows].reshape(-1),
                    "scales": s[row:row + rows].reshape(-1),
                    "block": block, "orig_len": a.size})
        row += rows
    return out


def dequantize_flat_batch(packed_list: Sequence[dict], *,
                          out_dtype=jnp.float32, interpret=None):
    """[packed_i] -> [x_i], fused when every item shares one block size."""
    if not packed_list:
        return []
    blocks = {int(p["block"]) for p in packed_list}
    if len(blocks) > 1:  # mixed block sizes cannot share a (rows, block)
        return [dequantize_flat(p, out_dtype=out_dtype, interpret=interpret)
                for p in packed_list]
    block = blocks.pop()
    qs = [np.asarray(p["q"]).reshape(-1, block) for p in packed_list]
    ss = [np.asarray(p["scales"]).reshape(-1, 1) for p in packed_list]
    q = qs[0] if len(qs) == 1 else np.concatenate(qs)
    s = ss[0] if len(ss) == 1 else np.concatenate(ss)
    x = _dequantize_rows(jnp.asarray(q), jnp.asarray(s), interpret)
    if out_dtype != jnp.float32:
        x = x.astype(out_dtype)
    x = np.asarray(x)
    if obs.recording():
        _count_copies("dequantize", [a for p in packed_list
                                     for a in (p["q"], p["scales"])],
                      q.nbytes + s.nbytes, x.nbytes)
    out, row = [], 0
    for p, qi in zip(packed_list, qs):
        rows = qi.shape[0]
        out.append(x[row:row + rows].reshape(-1)[: p["orig_len"]])
        row += rows
    return out


# ---------------------------------------------------------------------------
# batched top-k selection (the TopkCodec's fused encode path)
# ---------------------------------------------------------------------------

_jit_topk = jax.jit(kref.topk_rows_ref, static_argnames=("k",))


def topk_flat_batch(flats: Sequence, *, k_frac: float = 0.05):
    """[x_i] -> [{idx, vals, n}], the top-k sparse wire form, batched.

    Items are grouped by (length, k) — k is ``max(1, int(size *
    k_frac))``, a per-length wire constant — and each group runs as ONE
    stacked ``jax.lax.top_k`` dispatch on every backend. No padding is
    ever applied: padding would change k and the selection set, so
    unequal lengths simply land in different groups. Per-item results
    are bit-identical to the per-message ``top_k(|flat|)`` + gather path
    (same tie rule)."""
    if not flats:
        return []
    arrs = [np.asarray(x, np.float32).reshape(-1) for x in flats]
    groups: dict = {}
    for i, a in enumerate(arrs):
        k = max(1, int(a.size * k_frac))
        groups.setdefault((a.size, k), []).append(i)
    out = [None] * len(arrs)
    for (size, k), idxs in groups.items():
        stacked = jnp.asarray(np.stack([arrs[i] for i in idxs]))
        gi, gv = _jit_topk(stacked, k=k)
        gi, gv = np.asarray(gi), np.asarray(gv)
        if obs.recording():
            _count_copies("topk", [flats[i] for i in idxs], stacked.nbytes,
                          gi.nbytes + gv.nbytes)
        for row, i in enumerate(idxs):
            out[i] = {"idx": gi[row], "vals": gv[row], "n": size}
    return out


_jit_accumulate_ref = jax.jit(kref.fedavg_accumulate_ref)


def fedavg_accumulate_flat(acc, x, w, *, interpret=None):
    """One streaming fold ``acc + w * x`` over flat (T,) vectors via the
    fedavg_reduce accumulate kernel (same dispatch rule as the batched
    quantize wrappers)."""
    if interpret is None and _default_interpret():
        return _jit_accumulate_ref(jnp.asarray(acc, jnp.float32),
                                   jnp.asarray(x, jnp.float32), w)
    accp, orig = _pad_to(jnp.asarray(acc, jnp.float32), fr.COL_TILE)
    xp, _ = _pad_to(jnp.asarray(x, jnp.float32), fr.COL_TILE)
    return fr.fedavg_accumulate(accp, xp, w,
                                interpret=bool(interpret))[:orig]


# ---------------------------------------------------------------------------
# pytree-level API (used by compression/ and fl/)
# ---------------------------------------------------------------------------

def flatten_pytree(tree):
    """-> (flat f32 vector, unflatten_fn). Dtype-preserving on unflatten."""
    leaves, treedef = jax.tree.flatten(tree)
    sizes = [int(np.prod(l.shape)) for l in leaves]
    shapes = [l.shape for l in leaves]
    dtypes = [l.dtype for l in leaves]
    flat = jnp.concatenate([l.astype(jnp.float32).reshape(-1) for l in leaves]) \
        if leaves else jnp.zeros((0,), jnp.float32)

    def unflatten(vec):
        out = []
        off = 0
        for size, shape, dt in zip(sizes, shapes, dtypes):
            out.append(vec[off:off + size].reshape(shape).astype(dt))
            off += size
        return jax.tree.unflatten(treedef, out)

    return flat, unflatten


def quantize_pytree(tree, *, block: int = 256, interpret=None):
    flat, unflatten = flatten_pytree(tree)
    packed = quantize_flat(flat, block=block, interpret=interpret)
    return packed, unflatten


def fedavg_aggregate(updates: Sequence, weights, *, interpret=None):
    """Weighted average of N pytrees (normalised weights) via the Pallas
    reduction. Returns a pytree like updates[0]."""
    interpret = _default_interpret() if interpret is None else interpret
    weights = jnp.asarray(weights, jnp.float32)
    weights = weights / jnp.sum(weights)
    flats, unflatten = zip(*[flatten_pytree(u) for u in updates])
    stacked = jnp.stack(flats)  # (N, T)
    stacked, orig = _pad_to(stacked.T, fr.COL_TILE)  # pad T
    agg = fr.fedavg_reduce(stacked.T, weights, interpret=interpret)
    return unflatten[0](agg[:orig])


def fedavg_aggregate_q8(packed_list: Sequence[dict], weights, unflatten,
                        *, interpret=None):
    """Aggregate quantised client updates without materialising dequantised
    copies. packed_list: outputs of quantize_flat (same block/orig_len)."""
    interpret = _default_interpret() if interpret is None else interpret
    weights = jnp.asarray(weights, jnp.float32)
    weights = weights / jnp.sum(weights)
    block = packed_list[0]["block"]
    orig = packed_list[0]["orig_len"]
    q = jnp.stack([p["q"] for p in packed_list])  # (N, T') int8
    s = jnp.stack([p["scales"] for p in packed_list])  # (N, T'/block)
    t = q.shape[1]
    if t % fr.COL_TILE:
        pad = (-t) % fr.COL_TILE
        q = jnp.pad(q, ((0, 0), (0, pad)))
        s = jnp.pad(s, ((0, 0), (0, pad // block)))
    agg = fr.fedavg_reduce_q8(q, s, weights, block=block,
                              interpret=interpret)
    return unflatten(agg[:orig])
