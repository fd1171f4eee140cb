"""``correct`` holds for the program, and comes out false for the control
(the reference in bfloat16 in the program's place) and for each fault the
live cells can have, planted under the timed path of a whole run."""
import pytest

from tiny import run_tiny

MIXES = ["sync-grpc", "fedbuff-qsgd-s3"]


@pytest.mark.parametrize("mix", MIXES)
def test_the_program_is_correct(mix):
    res = run_tiny(mix)
    assert res["correct"], res["checks"]
    assert res["window_compiles"] == 0


@pytest.mark.parametrize("fault", [
    "control",         # the reference in bfloat16 in the program's place
    "unchanged",       # a step that returns its state unchanged
    "half_batch",      # each local step sees half its batch
    "half_updates",    # the hub averages half of the updates
    "altered_update",  # one client's update altered where it is made
])
@pytest.mark.parametrize("mix", MIXES)
def test_a_fault_is_caught(mix, fault):
    res = run_tiny(mix, fault=fault)
    assert not res["correct"], res["checks"]
