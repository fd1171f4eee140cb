"""Batched codec API: kernel-impl parity, encode_many == per-item encode
(wire bytes / charges / error-feedback state), roofline character."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compression.stages import QsgdCodec
from repro.core.channel import encode_many, make_channel
from repro.core.message import TensorPayload
from repro.kernels import ops, ref
from repro.kernels.quantize import ROW_TILE


def _flats(rng, lengths=(100, 2048, 2048 * 3 + 17)):
    return [jnp.asarray(rng.normal(size=n).astype(np.float32) * 3)
            for n in lengths]


def _trees(rng, n=3):
    return [{"w": rng.normal(size=(16 + i, 64)).astype(np.float32),
             "b": rng.normal(size=16 + i).astype(np.float32)}
            for i in range(n)]


def _wire_bytes(wire):
    return b"".join(
        bytes(b) if isinstance(b, (bytes, bytearray))
        else np.asarray(b).tobytes() for b in (wire.buffers or []))


# ---------------------------------------------------------------------------
# kernel parity: Pallas interpreter vs jitted ref vs NumPy twin
# ---------------------------------------------------------------------------

def test_quantize_flat_batch_parity_three_impls(rng):
    """One fused dispatch == per-item quantize, across every impl; the
    wire-critical int8 values agree bit-for-bit across all three impls
    on f32 input. Scales may differ by 1 ULP between the NumPy twin and
    XLA-compiled paths (XLA rewrites the constant division ``amax/127``
    as a reciprocal multiply), so they are held to <=1 ULP cross-impl
    and exactly equal batched-vs-single within one impl."""
    flats = _flats(rng)
    block = 256
    by_impl = {}
    for interpret in (True, None):  # Pallas interpreter / CPU jitted ref
        batch = ops.quantize_flat_batch(flats, block=block,
                                        interpret=interpret)
        single = [ops.quantize_flat(x, block=block, interpret=interpret)
                  for x in flats]
        for pb, ps in zip(batch, single):
            np.testing.assert_array_equal(np.asarray(pb["q"]),
                                          np.asarray(ps["q"]))
            np.testing.assert_array_equal(np.asarray(pb["scales"]),
                                          np.asarray(ps["scales"]))
            assert pb["orig_len"] == ps["orig_len"]
        by_impl[interpret] = batch
    # the NumPy twin, fed the same per-item row-aligned padding
    mult = block * ROW_TILE
    for x, pk in zip(flats, by_impl[None]):
        xp = np.zeros(-(-x.size // mult) * mult, np.float32)
        xp[: x.size] = np.asarray(x)
        qn, sn = ref.quantize_blocks_np(xp.reshape(-1, block))
        np.testing.assert_array_equal(qn.reshape(-1), np.asarray(pk["q"]))
        np.testing.assert_array_almost_equal_nulp(
            sn.reshape(-1), np.asarray(pk["scales"]), nulp=1)
    # and the interpreter agrees with the jitted ref
    for pa, pb in zip(by_impl[True], by_impl[None]):
        np.testing.assert_array_equal(np.asarray(pa["q"]),
                                      np.asarray(pb["q"]))
        np.testing.assert_array_almost_equal_nulp(
            np.asarray(pa["scales"]), np.asarray(pb["scales"]), nulp=1)


def test_dequantize_flat_batch_roundtrip_and_mixed_blocks(rng):
    flats = _flats(rng)
    packed = ops.quantize_flat_batch(flats, block=256)
    outs = ops.dequantize_flat_batch(packed)
    for x, y in zip(flats, outs):
        assert np.asarray(y).shape == np.asarray(x).shape
        amax = np.max(np.abs(np.asarray(x)))
        assert np.max(np.abs(np.asarray(y) - np.asarray(x))) <= amax / 254 \
            + 1e-7
    # mixed block sizes fall back to the per-item path, same results
    mixed = [ops.quantize_flat(flats[0], block=128),
             ops.quantize_flat(flats[1], block=512)]
    a, b = ops.dequantize_flat_batch(mixed)
    np.testing.assert_array_equal(
        np.asarray(a), np.asarray(ops.dequantize_flat(mixed[0])))
    np.testing.assert_array_equal(
        np.asarray(b), np.asarray(ops.dequantize_flat(mixed[1])))


# ---------------------------------------------------------------------------
# codec + channel surface: fused == sequential, bit-for-bit
# ---------------------------------------------------------------------------

def test_qsgd_encode_batch_matches_compress_loop(rng):
    trees = _trees(rng)
    a, b = QsgdCodec(block=256), QsgdCodec(block=256)
    payloads = [TensorPayload(t) for t in trees]
    states = [a.init_state(p) for p in payloads]  # live EF residuals
    fused = a.encode_batch(payloads, states)
    seq = [b.compress(p, s) for p, s in zip(payloads, states)]
    for (pf, sf, inf_f), (ps, ss, inf_s) in zip(fused, seq):
        for k in ("q", "scales"):
            np.testing.assert_array_equal(np.asarray(pf.packed[k]),
                                          np.asarray(ps.packed[k]))
        assert inf_f == inf_s
        np.testing.assert_array_equal(np.asarray(sf.error),
                                      np.asarray(ss.error))


def test_encode_many_matches_per_item_encode(rng):
    """Fan-out round (distinct peers): fused wire bytes, provenance,
    charges and per-peer EF residuals all equal the sequential path."""
    trees = _trees(rng)
    fused_ch = make_channel("protobuf", compression="qsgd")
    seq_ch = make_channel("protobuf", compression="qsgd")
    peers = [f"c{i}" for i in range(len(trees))]
    for _round in range(2):  # second round exercises non-None EF state
        encs = encode_many([(fused_ch, TensorPayload(t), p)
                            for t, p in zip(trees, peers)])
        refs = [seq_ch.encode(TensorPayload(t), p)
                for t, p in zip(trees, peers)]
        for enc, exp in zip(encs, refs):
            assert _wire_bytes(enc.wire) == _wire_bytes(exp.wire)
            assert enc.wire.stages == exp.wire.stages
            assert enc.wire.nbytes == exp.wire.nbytes
            assert enc.cost_s == pytest.approx(exp.cost_s)
            assert [(n, a) for n, _, a in enc.charges] == \
                   [(n, a) for n, _, a in exp.charges]
    for p in peers:
        np.testing.assert_array_equal(
            np.asarray(fused_ch.compress_stage._state[p].error),
            np.asarray(seq_ch.compress_stage._state[p].error))


def test_encode_many_keeps_same_peer_stream_sequential(rng):
    """Two encodes to ONE peer chain through the same EF residual; fusing
    them would decouple the chain, so encode_many must not."""
    trees = _trees(rng, n=2)
    trees[1] = jax.tree.map(np.copy, trees[0])  # same shapes -> shared state
    fused_ch = make_channel("protobuf", compression="qsgd")
    seq_ch = make_channel("protobuf", compression="qsgd")
    encs = encode_many([(fused_ch, TensorPayload(t), "s3") for t in trees])
    refs = [seq_ch.encode(TensorPayload(t), "s3") for t in trees]
    for enc, exp in zip(encs, refs):
        assert _wire_bytes(enc.wire) == _wire_bytes(exp.wire)
    np.testing.assert_array_equal(
        np.asarray(fused_ch.compress_stage._state["s3"].error),
        np.asarray(seq_ch.compress_stage._state["s3"].error))


def test_channel_decode_batch_inverts_encode_batch(rng):
    ch = make_channel("protobuf", compression="qsgd", wire_codec="zlib")
    trees = _trees(rng)
    encs = ch.encode_batch([(TensorPayload(t), f"c{i}")
                            for i, t in enumerate(trees)])
    plain = make_channel("protobuf")  # decodes purely by provenance
    decoded = plain.decode_batch([e.wire for e in encs])
    for t, (payload, cost) in zip(trees, decoded):
        assert cost > 0
        for k in t:
            assert np.asarray(payload.tree[k]).shape == t[k].shape
    # batched decode == per-wire decode, element for element
    for enc, (payload, _) in zip(encs, decoded):
        single, _ = plain.decode(enc.wire)
        for k in payload.tree:
            np.testing.assert_array_equal(np.asarray(payload.tree[k]),
                                          np.asarray(single.tree[k]))


# ---------------------------------------------------------------------------
# roofline: the fused quantize stage is bandwidth-bound
# ---------------------------------------------------------------------------

def test_fused_quantize_stage_is_bandwidth_bound():
    from repro.roofline.hlo_cost import (arithmetic_intensity, entry_cost,
                                         is_bandwidth_bound)
    c = jax.jit(ref.quantize_blocks_ref).lower(
        jax.ShapeDtypeStruct((512, 256), jnp.float32)).compile()
    cost = entry_cost(c.as_text())
    ai = arithmetic_intensity(cost)
    assert np.isfinite(ai)
    assert is_bandwidth_bound(cost, device_kind="TPU v5 lite"), (
        f"quantize stage should sit under the machine balance, got "
        f"intensity {ai:.1f} flops/byte")
