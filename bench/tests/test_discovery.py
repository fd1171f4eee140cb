"""A new configuration, traffic mix, metric or cell is found by its name:
adding one takes new files and new entries, and no edit to a file."""
import json
import shutil

import pytest

from bench import harness


@pytest.fixture
def tree(tmp_path):
    shutil.copytree(harness.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    return tmp_path


def test_new_files_are_found_by_name(tree):
    bench_dir = tree / "bench"
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    cfg = json.loads((bench_dir / "configs" / "resnet56.json").read_text())
    cfg["name"] = "resnet56-b"
    (bench_dir / "configs" / "resnet56-b.json").write_text(json.dumps(cfg))
    traffic = json.loads((bench_dir / "traffic" / "sync-grpc.json")
                         .read_text())
    traffic["num_clients"] = 3
    (bench_dir / "traffic" / "sync-three.json").write_text(
        json.dumps(traffic))
    (bench_dir / "limits" / "resnet56-b.sync-three.json").write_text(
        json.dumps({"loss_gap": 0.5}))
    (bench_dir / "metrics" / "rounds_done.py").write_text(
        "def read(run):\n    return len(run.rounds_in_window())\n")
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "resnet56-b", "source": "x",
                             "file": "bench/configs/resnet56-b.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "resnet56-b.sync-three",
                               "config": "resnet56-b",
                               "traffic": "sync-three", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "rounds_done", "unit": "rounds",
                               "better": "higher", "source": "host_clock",
                               "layer": "FL runtime", "moves": "round_s",
                               "workloads": ["resnet56-b.sync-three"]})

    cell, config, tr, limits = harness.load_cell(
        bench, "resnet56-b.sync-three", bench_dir)
    assert config["name"] == "resnet56-b"
    assert tr["num_clients"] == 3
    assert limits == {"loss_gap": 0.5}
    assert harness.load_entry(tr["entry"], bench_dir).run
    names = [m["name"] for m in harness.metrics_for(
        bench, "resnet56-b.sync-three", traced=True)]
    assert "rounds_done" in names and "quantize_roofline" not in names
    run = harness.Run("resnet56-b.sync-three", config, tr, 1, 1.0, True, 1,
                      {}, window=(0.0, 2.0), rounds=[(0.0, 1.0), (1.0, 2.0)])
    assert harness.load_reader("rounds_done", bench_dir)(run) == 2
    # nothing that was there has changed
    assert all(p.read_bytes() == b for p, b in before.items())


def test_every_named_metric_has_a_reader():
    bench = harness.load_benchmark()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
    for w in bench["workloads"]:
        cell, config, traffic, limits = harness.load_cell(bench, w["name"])
        assert harness.load_entry(traffic["entry"]).run
        assert limits and set(limits) <= {"step_loss_gap", "step_grad_gap",
                                          "loss_gap", "update_gap",
                                          "change_gap", "hub_weight_gap"}


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.BenchError):
        harness.load_peaks("TPU v99")
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
