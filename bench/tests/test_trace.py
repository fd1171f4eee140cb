"""Reduction of a device trace: busy union, idle share, time by name,
idle gaps labelled by the host span open during them."""
import pytest

from bench import trace
from bench.harness import Run, union_length
from bench.metrics import device_idle_pct  # noqa: F401  (import check)


def _raw():
    # window 0..10 s; two chips; chip 0 runs overlapping ops
    host = [("window", 0.0, 10.0), ("client_step", 0.5, 3.5),
            ("wire", 5.0, 6.0), ("hub", 8.0, 10.0)]
    dev0 = {"name": "/device:TPU:0",
            "ops": [("fusion.1", 1.0, 2.0), ("fusion.1", 1.5, 3.0),
                    ("fedavg_kernel", 6.5, 7.0), ("all-reduce.3", 9.0, 9.5),
                    ("outside", 10.5, 11.0)],
            "modules": [("jit_train_fn(1)", 1.0, 3.0),
                        ("jit_fedavg_reduce(2)", 6.5, 7.0)]}
    dev1 = {"name": "/device:TPU:1",
            "ops": [("fusion.1", 0.0, 1.0), ("all-reduce.3", 9.0, 10.0)],
            "modules": [("jit_train_fn(1)", 0.0, 1.0)]}
    return {"host": host, "devices": [dev0, dev1]}


def test_busy_union_and_idle_share():
    red = trace.reduce(_raw(), chips=2)
    # chip 0: [1,3] + [6.5,7] + [9,9.5] = 3.0; chip 1: 1 + 1 = 2.0
    assert red["window_s"] == 10.0
    assert red["busy_s"] == pytest.approx(2.5)
    run = Run("w", {}, {}, 0, 10.0, True, 2, {}, trace=red)
    from bench.metrics.device_idle_pct import read
    assert read(run) == pytest.approx(75.0)


def test_time_by_name():
    red = trace.reduce(_raw(), chips=2)
    # per chip means: fusion.1 = (1 + 1.5 + 1) / 2, clipped to the window
    assert red["ops"]["fusion.1"] == pytest.approx(1.75)
    assert "outside" not in red["ops"]
    assert red["ops"]["all-reduce.3"] == pytest.approx(0.75)
    count, secs = red["modules"]["jit_train_fn(1)"]
    assert (count, secs) == (pytest.approx(1.0), pytest.approx(1.5))


def test_idle_gaps_are_labelled_by_host_span():
    red = trace.reduce(_raw(), chips=1)
    # chip 0 gaps: [0,1] runtime (client_step covers 0.5 of 1), [3,6.5]
    # runtime (wire covers 1 of 3.5), [7,9] runtime (hub covers 1 of 2),
    # [9.5,10] hub (fully covered)
    gaps = dict((round(d, 6), lab) for lab, d in
                red["breakdown"]["idle_gaps"])
    assert gaps[0.5] == "hub"
    assert gaps[3.5] == "runtime"
    assert red["idle_by_layer"]["runtime"] == pytest.approx(6.5)
    assert red["breakdown"]["device_ops"][0][0] == "fusion.1"


def test_union_length():
    assert union_length([(0, 2), (1, 3), (5, 6)], 0, 10) == 4
    assert union_length([(0, 2), (1, 3), (5, 6)], 1.5, 5.5) == 2.0
    assert union_length([], 0, 1) == 0


def test_trace_without_window_is_refused():
    raw = _raw()
    raw["host"] = raw["host"][1:]
    with pytest.raises(ValueError):
        trace.reduce(raw, chips=1)
