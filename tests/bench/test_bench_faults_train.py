"""The training runner's comparison (its cell is not in BENCHMARK.json yet,
PERF.md section 7): a sound run is correct and each planted fault is not."""

import pytest

import tiny
from bench.calibrate import FAULTS, Patched


def test_train_sound():
    r = tiny.run("train", seed=3)
    assert r["correct"] is True, r["checks"]


@pytest.mark.parametrize("fault", ["train_state_unchanged",
                                   "train_half_batch", "ffn_answer_altered"])
def test_train_fault(fault):
    with Patched(FAULTS[fault]):
        r = tiny.run("train", seed=3)
    assert r["correct"] is False, r["checks"]
