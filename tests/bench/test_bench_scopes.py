"""The reduction from a device trace to the MoE step's stages (``nimble.*``
scopes), on a small synthetic step whose answers are counted by hand, and
its readers on the tiny one-chip step's own compiled HLO."""

import jax
import pytest

import tiny
from bench import harness, scopes
from bench import trace as tr
from bench.harness import Reading

Op = tr.Op
P = "jit(step)/shard_map/"

#: one synthetic step (seconds): stages back to back, the FFN after an idle
#: gap at [3.5, 4), a while loop of the dispatch spanning two planner ops
STEP = [
    ("fusion.1", 0.0, 0.5, "nimble.route"),
    ("sort.2", 0.5, 0.5, "nimble.pack"),
    ("all-reduce.3", 1.0, 0.2, "nimble.plan"),
    ("while.4", 1.2, 0.8, "nimble.dispatch"),
    ("fusion.5", 1.2, 0.4, "nimble.plan"),
    ("fusion.6", 1.6, 0.4, "nimble.plan"),
    ("collective-permute-done.7", 2.0, 1.0, "nimble.rounds"),
    ("fusion.8", 3.0, 0.5, "nimble.reassemble"),
    ("grouped_ffn.9", 4.0, 4.0, "nimble.ffn"),
    ("collective-permute-done.10", 8.0, 0.5, "nimble.rounds"),
    ("fusion.11", 8.5, 0.5, "nimble.combine"),
    ("copy.12", 9.0, 0.1, ""),
]
HOST = [Op("bench.window", 0.0, 10.0), Op("bench.dispatch", 0.0, 1.0),
        Op("bench.wait", 1.0, 8.5), Op("bench.sample", 9.5, 0.5)]

#: compiled-HLO text as XLA prints it, with the instructions the compiler
#: leaves without metadata
HLO = f"""HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {{
  %param_0 = f32[8]{{0}} parameter(0)
  ROOT %multiply.1 = f32[8]{{0}} multiply(%param_0, %param_0), metadata={{op_name="{P}nimble.combine/mul" stack_frame_id=3}}
}}

ENTRY %main (p0: f32[8], p1: s32[4], p2: f32[8]) -> (f32[8], s32[2,2], f32[8]) {{
  %p0 = f32[8]{{0}} parameter(0)
  %p1 = s32[4]{{0}} parameter(1)
  %p2 = f32[8]{{0}} parameter(2)
  %fusion.1 = f32[8]{{0}} fusion(%p0), kind=kLoop, calls=%fused_computation.1
  %all-reduce.3 = s32[4]{{0}} all-reduce(%p1), replica_groups={{{{0,1,2,3}}}}, to_apply=%add
  %reshape.4 = s32[2,2]{{1,0}} reshape(%all-reduce.3), metadata={{op_name="{P}nimble.dispatch/nimble.plan/all_gather"}}
  %copy-start.5 = (s32[2,2]{{1,0}}, s32[2,2]{{1,0}}, u32[]) copy-start(%reshape.4)
  %copy-done.5 = s32[2,2]{{1,0}} copy-done(%copy-start.5)
  %collective-permute-done.7 = f32[8]{{0}} collective-permute-done(%fusion.1), metadata={{op_name="{P}nimble.combine/nimble.rounds/ppermute"}}
  %dot.9 = f32[8]{{0}} dot(%p0, %p0), metadata={{op_name="transpose(jvp(nimble.ffn))/dot_general"}}
  %copy.12 = f32[8]{{0}} copy(%p2)
  ROOT %tuple = (f32[8]{{0}}, s32[2,2]{{1,0}}, f32[8]{{0}}) tuple(%collective-permute-done.7, %copy-done.5, %copy.12)
}}
"""


def _step():
    return [scopes.ScopedOp(n, s, d, "", sc) for n, s, d, sc in STEP]


def innermost_component_wins():
    assert scopes.scope_of(P + "nimble.combine/nimble.rounds/ppermute") == (
        "nimble.rounds")
    assert scopes.scope_of("transpose(jvp(nimble.ffn))/dot_general") == (
        "nimble.ffn")
    assert scopes.scope_of(P + "nimble.route/top_k") == "nimble.route"
    assert scopes.scope_of("jit(step)/broadcast_in_dim") == ""


def ops_outside_every_scope_are_unscoped():
    tagged = scopes.tag([Op("copy.12", 9.0, 0.1), Op("fusion.1", 0.0, 0.5)],
                        {"fusion.1": "nimble.route"})
    assert [o.scope for o in tagged] == ["", "nimble.route"]
    rows = scopes.top_scopes(_step(), 0.0, 10.0)
    assert rows[-1] == [scopes.UNSCOPED, pytest.approx(0.1)]
    assert scopes.scope_s(_step(), "", 0.0, 10.0) == pytest.approx(0.1)


def scopes_partition_the_busy_time():
    ops = _step()
    rows = scopes.top_scopes(ops, 0.0, 10.0)
    assert [s for s, _ in rows] == [
        "nimble.ffn", "nimble.rounds", "nimble.plan", "nimble.combine",
        "nimble.pack", "nimble.reassemble", "nimble.route",
        scopes.UNSCOPED]
    # rounds counts every exchange: dispatch's 1.0 s and combine's 0.5 s
    assert dict(rows)["nimble.rounds"] == pytest.approx(1.5)
    assert sum(s for _, s in rows) == pytest.approx(
        tr.busy_s(ops, 0.0, 10.0))
    assert tr.busy_s(ops, 0.0, 10.0) == pytest.approx(8.6)
    # clipped to a window that cuts the FFN
    assert scopes.scope_s(ops, "nimble.ffn", 5.0, 6.0) == pytest.approx(1.0)


def a_spanning_control_flow_op_is_not_counted_twice():
    ops = _step()
    # the while loop of the dispatch spans two planner ops: only they count
    assert scopes.scope_s(ops, "nimble.plan", 0.0, 10.0) == pytest.approx(1.0)
    assert scopes.scope_s(ops, "nimble.dispatch", 0.0, 10.0) == 0.0
    assert "nimble.dispatch" not in dict(scopes.top_scopes(ops, 0.0, 10.0))


def hlo_text_maps_instructions_to_scopes():
    assert scopes.hlo_scopes(HLO) == {
        "param_0": "nimble.combine",           # the fusion's own body
        "multiply.1": "nimble.combine",
        "p0": "nimble.combine",                # a parameter: its first user
        "p1": "nimble.plan",
        "p2": "",
        "fusion.1": "nimble.combine",          # its called computation
        "all-reduce.3": "nimble.plan",         # its user, the all-gather
        "reshape.4": "nimble.plan",
        "copy-start.5": "nimble.plan",         # its operand
        "copy-done.5": "nimble.plan",
        "collective-permute-done.7": "nimble.rounds",
        "dot.9": "nimble.ffn",
        "copy.12": "",                          # nothing to go by
        "tuple": "nimble.rounds",
    }


def hlo_text_is_read_instruction_by_instruction():
    got = list(scopes.instructions(HLO))
    assert [(c, n) for c, n, *_ in got[:2]] == [
        ("fused_computation.1", "param_0"), ("fused_computation.1",
                                             "multiply.1")]
    assert got[1][2:] == (P + "nimble.combine/mul", ["param_0", "param_0"],
                          True)
    by_name = {n: (op, refs, root) for _, n, op, refs, root in got}
    assert by_name["fusion.1"] == (None, ["p0", "fused_computation.1"],
                                   False)
    assert by_name["dot.9"][0] == "transpose(jvp(nimble.ffn))/dot_general"
    assert by_name["tuple"][2] and len(got) == 14


def todays_reduction_reads_as_before():
    ops = [Op(n, s, d) for n, s, d, _ in STEP]
    assert tr.top_ops(ops, k=3) == [
        ["grouped_ffn.9", pytest.approx(4.0)],
        ["collective-permute-done.7", pytest.approx(1.0)],
        ["fusion.1", pytest.approx(0.5)]]
    assert tr.idle_gaps(ops, HOST, 0.0, 10.0) == [
        ["bench.sample", pytest.approx(0.9)],
        ["bench.wait", pytest.approx(0.5)]]
    r = Reading(cell=None, ops=ops, t0=0.0, t1=10.0, calls=1, work={},
                peaks={})
    assert r.idle_share() == pytest.approx(14.0)


CASES = [innermost_component_wins, ops_outside_every_scope_are_unscoped,
         scopes_partition_the_busy_time,
         a_spanning_control_flow_op_is_not_counted_twice,
         hlo_text_maps_instructions_to_scopes,
         hlo_text_is_read_instruction_by_instruction,
         todays_reduction_reads_as_before]


@pytest.mark.parametrize("case", CASES, ids=[c.__name__ for c in CASES])
def test_scope_reduction(case):
    case()


@pytest.fixture(scope="module")
def tiny_step():
    """The tiny one-chip cell's runner after set-up, and its step's scopes."""
    mod = harness.load_module(harness.BENCH / "runners" / "moe_fwd.py")
    runner = mod.Runner(tiny.cell("moe1"), 3, jax.devices()[:1])
    runner.setup()
    return runner, scopes.hlo_scopes(scopes.step_hlo(runner))


def test_tiny_step_has_its_stages(tiny_step):
    _, hlo = tiny_step
    assert {"nimble.route", "nimble.ffn", "nimble.combine"} <= set(
        hlo.values())


def test_readers_find_the_step_of_a_traced_run(tiny_step, capsys):
    runner, hlo = tiny_step
    one = {s: next(n for n, v in hlo.items() if v == s)
           for s in ("nimble.route", "nimble.ffn", "nimble.combine")}
    # two calls: route 1 ms, FFN 8 ms, combine 2 ms, each
    ops = []
    for t in (0.0, 0.02):
        ops += [Op(one["nimble.route"], t, 0.001),
                Op(one["nimble.ffn"], t + 0.001, 0.008),
                Op(one["nimble.combine"], t + 0.009, 0.002)]
    metrics = harness.BENCH / "metrics"
    route = harness.load_module(metrics / "route_ms.fwd.py").read
    combine = harness.load_module(metrics / "combine_ms.fwd.py").read
    rounds = harness.load_module(metrics / "rounds_ms.fwd.py").read

    def traced(runner, r):      # the harness's frame, as the readers find it
        return route(r), combine(r), rounds(r)

    r = Reading(cell=None, ops=ops, t0=0.0, t1=0.04, calls=2, work={},
                peaks={})
    got = traced(runner, r)
    assert got[:2] == (pytest.approx(1.0), pytest.approx(2.0))
    assert got[2] is None                # no exchange on one chip
    # no traced frame, or no device ops: nothing to read, nothing raised
    r2 = Reading(cell=None, ops=ops, t0=0.0, t1=0.04, calls=2, work={},
                 peaks={})
    capsys.readouterr()
    assert route(r2) is None
    assert "WARNING: the scope metrics read nothing" in capsys.readouterr().err
    assert traced(runner, Reading(cell=None, ops=[], t0=0.0, t1=1.0,
                                  calls=2, work={}, peaks={})) == (
        None, None, None)
