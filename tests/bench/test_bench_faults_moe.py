"""The MoE block cells' comparison: a sound run is correct; the control (the
reference with three-pass bf16 matmuls in the program's place) and each
planted fault are not.  One chip in this process, four in another."""

import pytest

import tiny
from bench.calibrate import FAULTS, Patched
from bench_subprocess import tiny_run


@pytest.mark.parametrize("fault", ["ffn_answer_altered", "ffn_half_left_out",
                                   "moe_state_unchanged"])
def test_moe1_fault(fault):
    with Patched(FAULTS[fault]):
        r = tiny.run("moe1", seed=11)
    assert r["correct"] is False, r["checks"]


def test_moe1_control():
    r = tiny.run("moe1", seed=11, control=True)
    assert r["correct"] is False, r["checks"]


@pytest.mark.parametrize("args,correct", [
    ([], True),
    (["--control"], False),
    (["--fault", "exchange_left_out"], False),
])
def test_moe4(args, correct):
    r = tiny_run("moe4", args)
    assert r["correct"] is correct, r["checks"]
