"""HLO cost-walk correctness: trip counts, dots, collectives, DUS bytes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.hlo_cost import (collective_effective_bytes, entry_cost,
                                     parse_replica_groups)


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


def test_dot_flops_exact():
    def f(a, b):
        return a @ b
    c = _compile(f, jax.ShapeDtypeStruct((64, 128), jnp.float32),
                 jax.ShapeDtypeStruct((128, 32), jnp.float32))
    cost = entry_cost(c.as_text())
    expect = 2 * 64 * 128 * 32
    assert cost.flops == pytest.approx(expect, rel=0.3)


def test_scan_trip_count_multiplies():
    def step(x, w):
        return jnp.tanh(x @ w), None

    def g(x, ws):
        y, _ = jax.lax.scan(step, x, ws)
        return y.sum()

    costs = {}
    for n in (2, 8):
        c = _compile(g, jax.ShapeDtypeStruct((64, 64), jnp.float32),
                     jax.ShapeDtypeStruct((n, 64, 64), jnp.float32))
        costs[n] = entry_cost(c.as_text()).flops
    assert costs[8] / costs[2] == pytest.approx(4.0, rel=0.1)


def test_nested_scan_trip_counts():
    def inner(x, w):
        return x * w, None

    def outer(x, ws):
        def body(x, w_outer):
            y, _ = jax.lax.scan(inner, x, ws)
            return y + w_outer, None
        z, _ = jax.lax.scan(body, x, jnp.ones((5,)))
        return z.sum()

    c = _compile(lambda x, ws: outer(x, ws),
                 jax.ShapeDtypeStruct((128,), jnp.float32),
                 jax.ShapeDtypeStruct((3, 128), jnp.float32))
    cost = entry_cost(c.as_text())
    # 5 outer x (3 inner muls of 128) + 5 adds of 128 ~ 5*3*128 + 5*128
    assert cost.flops >= 5 * 3 * 128


def test_replica_group_parsing():
    size, groups = parse_replica_groups("replica_groups={{0,1},{2,3}}")
    assert size == 2 and groups == [[0, 1], [2, 3]]
    size, groups = parse_replica_groups(
        "replica_groups=[4,2]<=[2,4]T(1,0), attr=1")
    assert size == 2
    assert sorted(groups[0]) == [0, 4]


def test_collective_formulas():
    # ring all-reduce: 2(n-1)/n
    assert collective_effective_bytes("all-reduce", 1000, 1000, 4) == \
        pytest.approx(1500)
    assert collective_effective_bytes("all-gather", 1600, 400, 4) == \
        pytest.approx(1200)
    assert collective_effective_bytes("reduce-scatter", 400, 1600, 4) == \
        pytest.approx(1200)
    assert collective_effective_bytes("all-reduce", 1000, 1000, 1) == 0.0


def test_dus_counts_slice_not_buffer():
    def f(buf, x):
        def body(b, i):
            return jax.lax.dynamic_update_slice_in_dim(b, x, i * 4, 0), None
        out, _ = jax.lax.scan(body, buf, jnp.arange(64))
        return out.sum()

    c = _compile(f, jax.ShapeDtypeStruct((4096, 256), jnp.float32),
                 jax.ShapeDtypeStruct((4, 256), jnp.float32))
    cost = entry_cost(c.as_text())
    buf_bytes = 4096 * 256 * 4
    # 64 iterations touching a 4x256 slice each must NOT count 64 full buffers
    assert cost.hbm_bytes < 10 * buf_bytes


def test_peaks_keyed_by_device_kind():
    from repro.roofline import peaks_for
    from repro.roofline.hlo_cost import machine_balance
    v5e = peaks_for("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    assert machine_balance("TPU v5 lite") == pytest.approx(240.5, abs=0.1)
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("cpu")
