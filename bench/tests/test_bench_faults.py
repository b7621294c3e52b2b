"""A whole run, with the look for a chip skipped, at a small size on the
CPU: sound, it is correct; with the timed path broken underneath, each
fault a cell can have turns ``correct`` false under the cell's limits."""
import pytest


@pytest.mark.parametrize("fault", [None, "unchanged_state", "half_batch"])
def test_train_faults(small_run, fault):
    out = small_run("train.mamba-110m.pack4k", fault)
    assert out["correct"] is (fault is None), out["checks"]
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0
    assert out["compiles_in_window"] == 0


@pytest.mark.parametrize("fault", [None, "altered_token"])
def test_serve_faults(small_run, fault):
    out = small_run("serve.mamba-110m.chat", fault, seconds="2")
    assert out["correct"] is (fault is None), out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    # warm-up from the engine's own sizes leaves nothing to build
    assert out["compiles_in_window"] == 0


def test_train_control_fails(small_run):
    """The reference in float8 put in the program's place fails the cell's
    limits (the size here is small, so only its direction is checked)."""
    out = small_run("train.mamba-110m.pack4k", "control_fp8")
    assert out["correct"] is False, out["checks"]
