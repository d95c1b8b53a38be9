"""The All-to-Allv cell's comparison: a sound run is correct; the control
(the reference moving the payload as bfloat16 in the program's place) and
each planted fault are not.  Four virtual CPU devices, in a process each."""

import pytest

from bench_subprocess import tiny_run


@pytest.mark.parametrize("args,correct", [
    ([], True),
    (["--control"], False),
    (["--fault", "exchange_left_out"], False),
    (["--fault", "a2av_answer_altered"], False),
    (["--fault", "a2av_half_left_out"], False),
])
def test_a2av(args, correct):
    r = tiny_run("a2av", args)
    assert r["correct"] is correct, r["checks"]
