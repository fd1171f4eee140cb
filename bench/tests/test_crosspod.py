"""The cross-pod cell: found by its names, its counts and readers by hand,
and at a tiny size on four host devices (``tiny_crosspod.py``, in a
process of its own): the program is correct, and each fault, planted
under the timed path, fails a limit."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness, hubert_flops
from bench.harness import Run
from bench.reference import hubert as ref_hubert

CELL = "hubert-xlarge.crosspod-int8"
FAULTS = [
    "sum",         # the pods' deltas summed, not averaged
    "skipped",     # no exchange: the anchor stays as it was
    "all_frames",  # the loss over every frame, not the masked ones
    "half_batch",  # each local step sees half of its batch
    "local_only",  # no exchange between the chips: one pod's own delta
    "control",     # the reference one precision lower in the program's place
]


@pytest.fixture(scope="module")
def tiny_runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, str(harness.BENCH_DIR / "tests" / "tiny_crosspod.py"),
         "none", *FAULTS], env=env, capture_output=True, text=True,
        timeout=1200)
    assert p.returncode == 0, p.stderr[-4000:]
    return {r["fault"]: r for r in map(json.loads,
                                       p.stdout.strip().splitlines())}


def test_the_program_is_correct(tiny_runs):
    res = tiny_runs["none"]
    assert res["correct"], res["checks"]
    assert res["window_compiles"] == 0


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_is_caught(tiny_runs, fault):
    res = tiny_runs[fault]
    assert not res["correct"], res["checks"]


def test_the_cell_is_found_by_its_names():
    bench = harness.load_benchmark()
    cell, config, traffic, limits = harness.load_cell(bench, CELL)
    assert (cell["chips"], config["name"], traffic["entry"]) == (
        4, "hubert-xlarge", "crosspod")
    assert set(limits) == {"loss_gap", "update_gap", "change_gap"}
    assert harness.load_entry(traffic["entry"]).run
    untraced = {m["name"] for m in harness.metrics_for(bench, CELL, False)}
    assert untraced == {"round_s", "setup_s"}
    traced = {m["name"] for m in harness.metrics_for(bench, CELL, True)}
    assert traced == {"device_idle_pct", "crosspod_mfu",
                      "crosspod_collective_pct", "exchange_roofline"}


def test_config_counts():
    cfg = json.loads((harness.BENCH_DIR / "configs" / "hubert-xlarge.json")
                     .read_text())
    assert ref_hubert.param_count(cfg) == cfg["params"] == 964_321_152
    assert ref_hubert.frames(cfg, 160_000) == 499
    # about 4.27 TFLOP forward per pod step of 4 clips, the encoder's
    # 48 x 19.66M weights at 2 per frame and weight being most of it
    assert hubert_flops.train_step(cfg, 4, 160_000) == pytest.approx(
        12.81e12, rel=0.001)


def test_flops_by_hand():
    cfg = {"conv_channels": 2, "conv_kernels": [4, 2], "conv_strides": [2, 2],
           "d_model": 4, "pos_conv_kernel": 3, "pos_conv_groups": 2,
           "num_layers": 1, "d_ff": 8, "final_dim": 3, "num_clusters": 5}
    # 10 samples -> 4 -> 2 frames
    cnn = 2 * 4 * 4 * 1 * 2 + 2 * 2 * 2 * 2 * 2
    proj = 2 * 2 * 2 * 4
    pos = 2 * 2 * 3 * 2 * 4
    layer = 2 * 2 * (4 * 16 + 2 * 4 * 8) + 4 * 2 * 2 * 4
    head = 2 * 2 * 4 * 3 + 2 * 2 * 3 * 5
    assert hubert_flops.forward(cfg, 10) == cnn + proj + pos + layer + head
    assert hubert_flops.train_step(cfg, 3, 10) == 9 * hubert_flops.forward(
        cfg, 10)


def test_readers_by_hand():
    trace = {"window_s": 2.0, "busy_s": 1.0, "modules": {},
             "ops": {"all-gather.1": 0.2, "all-gather-start.2": 0.05,
                     "fusion.3": 0.75}}
    run = Run(CELL, {}, {}, 1, 2.0, True, 4,
              {"bf16_flops": 1e12, "ici_bits_per_s": 8e9},
              window=(0.0, 2.0), trace=trace, flops_per_step=1e11,
              client_steps=[-1.0, 0.5, 1.0, 1.5, 3.0],
              kernel_calls=[(1.0, "exchange", 10 ** 8),
                            (2.5, "exchange", 10 ** 8)])
    read = lambda name: harness.load_reader(name)(run)  # noqa: E731
    assert read("crosspod_collective_pct") == pytest.approx(25.0)
    # 1e8 B in the window over 1e9 B/s, over 0.25 s of all-gathers
    assert read("exchange_roofline") == pytest.approx(40.0)
    # 3 steps of 1e11 over 2 s x 4 chips x 1e12
    assert read("crosspod_mfu") == pytest.approx(3.75)
    run.trace = None
    assert read("crosspod_collective_pct") is None
    assert read("exchange_roofline") is None
