"""The cross-pod FL round of ``repro.launch.fl_round`` on four host
devices, in a process of its own (``_crosspod_child.py``): two rounds at
a small size against ``bench/reference/crosspod.py``, leaf by leaf; the
exchange's collectives; the spans and counters."""
import json
import math
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

CHILD = Path(__file__).with_name("_crosspod_child.py")
PODS, ROUNDS, LOCAL_STEPS = 4, 2, 2


@pytest.fixture(scope="module")
def child():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(CHILD)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_round_losses_match_the_reference(child):
    np.testing.assert_allclose(child["losses"], child["ref_losses"],
                               rtol=1e-5)


def test_round_anchors_match_the_reference_leaf_by_leaf(child):
    # a pod's delta that lands within round-off of a rounding tie may take
    # the next int8 code: that moves an element by one level of its leaf's
    # shared scale, max|delta| / 127 / pods, under 2/127 of how far the
    # leaf moved; leaves barely moved (a key bias, whose gradient is
    # nought) are measured against the median leaf's movement
    for r, leaves in enumerate(child["leaves"]):
        med = float(np.median([moved for _, moved in leaves]))
        for i, (err, moved) in enumerate(leaves):
            assert err <= 2 / 127 * max(moved, med), (r, i, err, moved)


def test_the_exchange_carries_int8(child):
    """One all-gather per parameter leaf, of the pods' int8 codes; no
    float delta of a leaf's shape crosses the pod axis."""
    stacked = sorted([PODS] + s for s in child["leaf_shapes"])
    exchanged = [(dt, shape) for dt, shape in child["gathers"]
                 if shape in stacked]
    assert sorted(shape for _, shape in exchanged) == stacked
    assert {dt for dt, _ in exchanged} == {"s8"}


def test_exchange_bytes_are_the_int8_leaves(child):
    sizes = [math.prod(s) for s in child["leaf_shapes"]]
    assert child["exchange_bytes"] == sum(sizes) + 4 * len(sizes)
    assert child["counts"]["crosspod.exchange_bytes"] == (
        ROUNDS * child["exchange_bytes"])


def test_spans_nest_in_the_round(child):
    assert child["spans"] == {"crosspod.round": None,
                              "crosspod.dispatch": "crosspod.round",
                              "crosspod.input": "crosspod.round",
                              "crosspod.sync": "crosspod.round"}


def test_counters_per_round(child):
    c = child["counts"]
    assert c["crosspod.rounds"] == ROUNDS
    assert c["crosspod.local_steps"] == ROUNDS * PODS * LOCAL_STEPS
    assert c["hubert.frames"] == ROUNDS * child["frames"]
    assert c["hubert.masked_frames"] == sum(child["masked"])
    # round 0's batches at start, then the next round's in each round
    assert child["h2d"] == [["crosspod.input", child["batch_bytes"]]] * (
        ROUNDS + 1)


def test_the_command_runs_rounds_and_writes_spans(tmp_path):
    """``python -m repro.launch.fl_round``, the small configuration on four
    host devices."""
    spans = tmp_path / "spans.jsonl"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(CHILD.parents[1] / "src"))
    p = subprocess.run(
        [sys.executable, "-m", "repro.launch.fl_round", "--smoke",
         "--rounds", "2", "--local-steps", "2", "--pod-batch", "1",
         "--samples", "10320", "--spans", str(spans)],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    losses = json.loads(p.stdout.strip().splitlines()[-1])["losses"]
    assert len(losses) == 2 and all(math.isfinite(l) for l in losses)
    records = [json.loads(line) for line in spans.read_text().splitlines()]
    assert sum(r.get("span") == "crosspod.round" for r in records) == 2
    assert sum(r["n"] for r in records
               if r.get("count") == "crosspod.rounds") == 2
