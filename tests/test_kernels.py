"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.fedavg_reduce import (COL_TILE, fedavg_accumulate,
                                         fedavg_reduce, fedavg_reduce_q8)
from repro.kernels.quantize import ROW_TILE, dequantize_blocks, quantize_blocks


@pytest.mark.parametrize("rows,block", [(8, 128), (16, 256), (32, 512),
                                        (8, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_matches_ref(rows, block, dtype, rng):
    x = jnp.asarray(rng.normal(size=(rows, block)) * 3).astype(dtype)
    q, s = quantize_blocks(x, interpret=True)
    qr, sr = ref.quantize_blocks_ref(x)
    # interpret-mode vs jit f32 contraction order can flip exact .5 ties
    # for bf16 inputs: allow 1 quantisation level there, exact otherwise
    if dtype == jnp.bfloat16:
        assert np.max(np.abs(np.asarray(q, np.int32)
                             - np.asarray(qr, np.int32))) <= 1
    else:
        np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    xd = dequantize_blocks(q, s, interpret=True)
    xdr = ref.dequantize_blocks_ref(q, sr)  # same q: dequant parity
    np.testing.assert_allclose(np.asarray(xd), np.asarray(xdr), rtol=1e-6)


def test_quantize_roundtrip_error_bounded(rng):
    x = jnp.asarray(rng.normal(size=(16, 256)).astype(np.float32))
    q, s = quantize_blocks(x, interpret=True)
    xd = dequantize_blocks(q, s, interpret=True)
    # error per element bounded by scale/2 = amax/254
    amax = np.max(np.abs(np.asarray(x)), axis=1, keepdims=True)
    assert np.all(np.abs(np.asarray(xd - x)) <= amax / 254 + 1e-7)


def test_quantize_zero_block():
    x = jnp.zeros((8, 256), jnp.float32)
    q, s = quantize_blocks(x, interpret=True)
    assert np.all(np.asarray(q) == 0)
    xd = dequantize_blocks(q, s, interpret=True)
    assert np.all(np.asarray(xd) == 0)


@pytest.mark.parametrize("n,t", [(2, COL_TILE), (5, 2 * COL_TILE),
                                 (16, 4 * COL_TILE)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fedavg_matches_ref(n, t, dtype, rng):
    u = jnp.asarray(rng.normal(size=(n, t))).astype(dtype)
    w = jnp.asarray(rng.uniform(0.5, 2.0, size=n).astype(np.float32))
    out = fedavg_reduce(u, w, interpret=True)
    expect = ref.fedavg_reduce_ref(u, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("n,t,block", [(3, COL_TILE, 256), (7, 2 * COL_TILE, 512)])
def test_fedavg_q8_matches_ref(n, t, block, rng):
    qs, ss = [], []
    for i in range(n):
        x = jnp.asarray(rng.normal(size=(t,)).astype(np.float32))
        p = ops.quantize_flat(x, block=block, interpret=True)
        qs.append(p["q"])
        ss.append(p["scales"])
    q, s = jnp.stack(qs), jnp.stack(ss)
    w = jnp.asarray(rng.uniform(0.5, 2.0, size=n).astype(np.float32))
    out = fedavg_reduce_q8(q, s, w, block=block, interpret=True)
    expect = ref.fedavg_reduce_q8_ref(q, s, w, block=block)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-5)


def test_pytree_aggregate_weighted_mean(rng):
    t1 = {"a": jnp.ones((37, 5)), "b": jnp.zeros((9,))}
    t2 = {"a": jnp.zeros((37, 5)), "b": jnp.ones((9,))}
    agg = ops.fedavg_aggregate([t1, t2], [3.0, 1.0])
    np.testing.assert_allclose(np.asarray(agg["a"]), 0.75, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(agg["b"]), 0.25, rtol=1e-5)


def test_flatten_roundtrip_mixed_dtypes(rng):
    tree = {"w": jnp.asarray(rng.normal(size=(4, 3)).astype(np.float32)),
            "b": jnp.asarray(rng.normal(size=(7,)).astype(np.float32)).astype(jnp.bfloat16)}
    flat, unflatten = ops.flatten_pytree(tree)
    rec = unflatten(flat)
    assert rec["b"].dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(rec["w"]), np.asarray(tree["w"]))


@pytest.mark.parametrize("fn", [quantize_blocks, dequantize_blocks,
                                fedavg_reduce, fedavg_reduce_q8,
                                fedavg_accumulate])
def test_kernel_entry_points_default_to_compiled(fn):
    import inspect
    assert inspect.signature(fn).parameters["interpret"].default is False


def test_ops_refuse_backends_without_a_kernel_path(monkeypatch, rng):
    x = rng.normal(size=300).astype(np.float32)
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="target TPU"):
        ops.quantize_flat_batch([x])
    with pytest.raises(RuntimeError, match="target TPU"):
        ops.fedavg_aggregate([{"x": jnp.asarray(x)}], [1.0])


def test_topk_batch_is_lax_top_k_on_every_backend(rng):
    x = rng.normal(size=(3, 200)).astype(np.float32)
    out = ops.topk_flat_batch(list(x), k_frac=0.1)
    for row, o in zip(x, out):
        want = np.argsort(-np.abs(row), kind="stable")[:20]
        np.testing.assert_array_equal(o["idx"], want)
        np.testing.assert_array_equal(o["vals"], row[want])
