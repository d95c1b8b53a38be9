"""A run's refusals and the shape of its result line."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import tiny
from bench import harness

ROOT = harness.ROOT


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "moe8e-1chip-hot0.9",
         "--seed", "2147483701", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_refuses_where_only_the_benchmark_is(tmp_path):
    bm = harness.load_json(ROOT / "BENCHMARK.json")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in bm["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_find_chips_refuses_cpu_and_too_few():
    with pytest.raises(harness.NoChip):
        harness.find_chips(1)


@pytest.fixture(scope="module")
def untraced():
    return tiny.run("moe1", seed=2147483701)


def test_result_line_shape(untraced):
    r = json.loads(json.dumps(untraced))
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "checks"
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"fwd_tokens_per_s", "setup_s"}
    for m in r["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(r["device"]) == {"platform", "kind", "count",
                                "memory_peak_bytes"}
    assert r["checks"]["out_gap"]["limit"] > r["checks"]["out_gap"]["value"]


def test_traced_result_line_shape():
    r = tiny.run("moe1", seed=5, trace=True)
    assert r["correct"] is True
    # the CPU has no device plane: every per-layer reader finds nothing
    assert r["metrics"] == {}
    assert set(r["device"]) >= {"busy_s", "window_s"}
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(r)[-1] == "checks"


def test_same_seed_same_inputs_other_seed_other_inputs():
    import jax.numpy as jnp

    c = tiny.cell("moe1")
    mod = harness.load_module(harness.BENCH / "runners" / "moe_fwd.py")
    import jax

    devs = jax.devices()[:1]
    a, b, other = (mod.Runner(c, s, devs) for s in (3, 3, 4))
    for d in (a, b, other):
        d.setup()
    assert bool(jnp.array_equal(a.xs[0], b.xs[0]))
    assert bool(jnp.array_equal(a.params["wg"], b.params["wg"]))
    assert not bool(jnp.array_equal(a.xs[0], other.xs[0]))
