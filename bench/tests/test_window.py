"""Windowing: a stall inside the measured window shows in round_s and in
update_p95_s; set-up is not in the window."""
import time

import pytest

import run as bench_run
from tiny import run_tiny


@pytest.mark.parametrize("mix", ["sync-grpc", "fedbuff-qsgd-s3"])
def test_a_stall_in_the_window_raises_round_and_tail(mix, monkeypatch):
    base = run_tiny(mix, seconds=1.5)["metrics"]
    from repro.fl.client import FLClient
    orig = FLClient.local_train
    state = {"open": False}
    opened = bench_run.Context.window_open

    def window_open(self):
        state["open"] = True
        return opened(self)

    def local_train(self, params, steps):
        if state["open"]:
            time.sleep(0.25)
        return orig(self, params, steps)

    monkeypatch.setattr(bench_run.Context, "window_open", window_open)
    monkeypatch.setattr(FLClient, "local_train", local_train)
    slow = run_tiny(mix, seconds=1.5)["metrics"]
    assert slow["update_p95_s"]["value"] > base["update_p95_s"]["value"] + 0.2
    assert slow["round_s"]["value"] > base["round_s"]["value"] + 0.2
    # the stall is not set-up
    assert slow["setup_s"]["value"] < base["setup_s"]["value"] + 5.0
