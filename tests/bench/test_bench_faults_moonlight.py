"""The Moonlight cell's comparison at a tiny size on the CPU: a sound run is
correct; the control (the reference with fp8-mantissa matmul inputs in the
program's place) and each planted fault are not.  The tiny cell keeps the
cell's runner, reference, traffic pattern and limits and shrinks only the
widths, the depth and the sequence."""

import time

import jax
import pytest

import tiny
from bench import calibrate_lm, harness, scopes  # noqa: F401 (lm_fwd faults)
from bench.calibrate import FAULTS, Patched
from bench.trace import Op

CELL = "moonlight-1chip-s4096-hot0.5"


def tiny_cell() -> harness.Cell:
    bm = harness.load_json(harness.ROOT / "BENCHMARK.json")
    entry = next(w for w in bm["workloads"] if w["name"] == CELL)
    config = dict(
        harness.load_json(harness.BENCH / "configs" / f"{entry['config']}.json"),
        hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, n_routed_experts=8, num_experts_per_tok=3,
        moe_intermediate_size=32, intermediate_size=96, num_hidden_layers=3,
        vocab_size=128, router_margin=[3.0, 2.0, 1.0])
    traffic = dict(
        harness.load_json(harness.BENCH / "traffic" / f"{entry['traffic']}.json"),
        batch=2, seq=128, payload_sets=2)
    return harness.Cell(
        name=CELL, chips=1, config=config, traffic=traffic,
        limits=harness.load_json(harness.BENCH / "limits" / f"{CELL}.json"),
        end_to_end=[m for m in bm["end_to_end"] if harness.applies(m, CELL)],
        per_layer=[m for m in bm["per_layer"] if harness.applies(m, CELL)])


def run(after_setup=None) -> dict:
    # CPU programs in the checkout's compile cache serve no chip run
    harness.enable_compile_cache = lambda: None
    return harness.run(tiny_cell(), 2200000017, 0.2, False,
                       time.perf_counter(), devices=jax.devices()[:1],
                       peaks=tiny.PEAKS, after_setup=after_setup)


def swap_router_experts(runner):
    """The second MoE layer's router swaps the hot expert's vector with
    another's: that layer routes as the traffic did not draw."""
    r = runner.params["blocks"]["router"]
    runner.params["blocks"]["router"] = r.at[1, :, [0, 1]].set(r[1, :, [1, 0]])


def test_sound_run_is_correct():
    r = run()
    assert r["correct"] is True, r["checks"]
    assert r["checks"]["route_miss"]["value"] == 0.0


def test_readers_find_attention_and_shared_experts():
    """The runner's step carries every MoE stage's scope and the two new
    ones; their readers take a traced run's ops by those scopes (the CPU
    has no device plane, so the ops here are made up: 3 ms of attention
    and 1 ms of shared experts in each of two calls)."""
    mod = harness.load_module(harness.BENCH / "runners" / "lm_fwd.py")
    runner = mod.Runner(tiny_cell(), 3, jax.devices()[:1])
    runner.setup()
    hlo = scopes.hlo_scopes(scopes.step_hlo(runner))
    assert {"nimble.attn", "nimble.shared", "nimble.route", "nimble.ffn",
            "nimble.combine"} <= set(hlo.values())
    one = {s: next(n for n, v in hlo.items() if v == s)
           for s in ("nimble.attn", "nimble.shared")}
    ops = []
    for t in (0.0, 0.02):
        ops += [Op(one["nimble.attn"], t, 0.003),
                Op(one["nimble.shared"], t + 0.003, 0.001)]
    metrics = harness.BENCH / "metrics"
    attn = harness.load_module(metrics / "attn_ms.fwd.py").read
    shared = harness.load_module(metrics / "shared_ms.fwd.py").read

    def traced(runner, r):      # the harness's frame, as the readers find it
        return attn(r), shared(r)

    got = traced(runner, harness.Reading(cell=None, ops=ops, t0=0.0, t1=0.04,
                                         calls=2, work={}, peaks={}))
    assert got == (pytest.approx(3.0), pytest.approx(1.0))


def test_control_is_not_correct():
    r = run(after_setup=lambda d: d.control())
    assert r["correct"] is False, r["checks"]


@pytest.mark.parametrize("fault", ["shared_left_out", "bias_in_gate_weights",
                                   "ffn_answer_altered"])
def test_fault_is_not_correct(fault):
    with Patched(FAULTS[fault]):
        r = run()
    assert r["correct"] is False, r["checks"]


def test_router_agreement_below_one_is_not_correct():
    r = run(after_setup=swap_router_experts)
    assert r["checks"]["route_miss"]["value"] > 0, r["checks"]
    assert r["correct"] is False, r["checks"]
