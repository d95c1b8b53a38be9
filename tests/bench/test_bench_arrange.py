"""The ``arrange_ms.fwd`` reader: device time per call in the row arrangement
around the grouped FFN kernel (``nimble.arrange``), and nothing where the step
has no such scope, as a program from before the scope has not."""

import jax
import pytest

import tiny
from bench import harness, scopes
from bench import trace as tr
from bench.harness import Reading

Op = tr.Op
READ = harness.load_module(
    harness.BENCH / "metrics" / "arrange_ms.fwd.py").read

#: two calls (seconds): the input gather, the kernel, the output gather
STEP = [
    ("fusion.5", 0.000, 0.0015, "nimble.arrange"),
    ("grouped_ffn.1", 0.0015, 0.004, "nimble.ffn"),
    ("fusion.6", 0.0055, 0.0005, "nimble.arrange"),
    ("fusion.10", 0.006, 0.001, "nimble.combine"),
    ("fusion.5", 0.020, 0.0015, "nimble.arrange"),
    ("grouped_ffn.1", 0.0215, 0.004, "nimble.ffn"),
    ("fusion.6", 0.0255, 0.0005, "nimble.arrange"),
    ("fusion.10", 0.026, 0.001, "nimble.combine"),
]


def _reading(step):
    return Reading(cell=None, ops=[Op(n, s, d) for n, s, d, _ in step],
                   t0=0.0, t1=0.04, calls=2, work={}, peaks={})


def _scoped(monkeypatch, step):
    tagged = [scopes.ScopedOp(n, s, d, "", sc) for n, s, d, sc in step]
    monkeypatch.setattr(scopes, "scoped_ops", lambda r: tagged)


def test_arrange_reads_its_scope_per_call(monkeypatch):
    _scoped(monkeypatch, STEP)
    assert READ(_reading(STEP)) == pytest.approx(2.0)


@pytest.mark.parametrize("step", [
    [o for o in STEP if o[3] != "nimble.arrange"],
    [(n, s, d, "nimble.ffn" if sc == "nimble.arrange" else sc)
     for n, s, d, sc in STEP],
], ids=["no-arrange-op", "arrangement-inside-ffn"])
def test_arrange_reads_none_without_its_scope(monkeypatch, step):
    _scoped(monkeypatch, step)
    assert READ(_reading(step)) is None


def test_arrange_reads_none_without_scoped_ops(monkeypatch):
    monkeypatch.setattr(scopes, "scoped_ops", lambda r: None)
    assert READ(_reading(STEP)) is None


def test_arrange_finds_the_tiny_step():
    """The tiny one-chip cell's compiled step holds the arrangement, and the
    reader finds it from the harness's ``traced`` frame."""
    mod = harness.load_module(harness.BENCH / "runners" / "moe_fwd.py")
    runner = mod.Runner(tiny.cell("moe1"), 3, jax.devices()[:1])
    runner.setup()
    hlo = scopes.hlo_scopes(scopes.step_hlo(runner))
    one = next(n for n, s in hlo.items() if s == "nimble.arrange")
    r = Reading(cell=None, ops=[Op(one, 0.0, 0.003), Op(one, 0.02, 0.003)],
                t0=0.0, t1=0.04, calls=2, work={}, peaks={})

    def traced(runner, r):      # the harness's frame, as the readers find it
        return READ(r)

    assert traced(runner, r) == pytest.approx(3.0)
