"""``chip_smoke.py`` on the CPU: its phases at a reduced granite config, the
multi-chip phase on 4 virtual devices, and its refusal to run off a TPU."""

import dataclasses
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.kernels import tpu_kernels
from repro.launch import compile_cache
from repro.models.registry import build_model
from repro.serve.engine import ServeEngine

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


smoke = _load_smoke()


@pytest.fixture(scope="module")
def granite_small():
    cfg = get_config(smoke.ARCH).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_serve_and_agree_phases(granite_small):
    cfg, model, params = granite_small
    prompts = np.random.default_rng(0).integers(0, cfg.vocab, (2, 16),
                                                dtype=np.int32)
    engine = ServeEngine(model, params, max_len=16 + 4)
    assert smoke.phase_serve(engine, prompts, n_new=4)
    assert smoke.phase_agree(model, engine, prompts)


def test_forward_phase(granite_small):
    cfg, model, params = granite_small
    toks = np.random.default_rng(1).integers(0, cfg.vocab, (1, 64),
                                             dtype=np.int32)
    ok, kernels = smoke.phase_forward(model, params, toks)
    assert ok
    assert kernels == set()          # the CPU compiles no TPU kernel


def test_flash_phase(granite_small):
    """The kernel (interpret mode on the CPU) over two KV blocks."""
    cfg, _, _ = granite_small
    assert smoke.phase_flash(cfg, seq=256)


def test_planner_phase():
    assert smoke.phase_planner(n=4, group_size=2, max_chunks=32,
                               hotspot=0.9)


def test_tpu_kernels_parses_custom_calls():
    hlo = (
        '  %grouped_ffn.1 = f32[8,128]{1,0} custom-call(%a), '
        'custom_call_target="tpu_custom_call"\n'
        '  ROOT %flash_attention = f32[1,1,8,128]{3,2,1,0} custom-call(%b), '
        'custom_call_target="tpu_custom_call"\n'
        '  %dot.3 = f32[8,8]{1,0} dot(%a, %b)\n'
    )
    assert tpu_kernels(hlo) == {"grouped_ffn", "flash_attention"}


def test_main_refuses_a_cpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "platform 'cpu'" in err


def test_main_refuses_without_the_package(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    lone.write_text(open(os.path.join(ROOT, "chip_smoke.py")).read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, str(lone)], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_compile_cache_location(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable_compile_cache()
    assert path == str(Path(ROOT).resolve() / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]


@pytest.mark.timeout(600)
def test_four_chip_phase_on_virtual_devices():
    """The --chips 4 path on 4 CPU devices at a reduced granite config."""
    code = (
        "import dataclasses, sys\n"
        "import chip_smoke as s\n"
        "from repro.configs.base import get_config\n"
        "cfg = dataclasses.replace(get_config(s.ARCH).reduced(),\n"
        "                          n_experts=8, top_k=2)\n"
        "ok = s.run_four_chips(cfg, chunk_elems=256, moe_tokens=64,\n"
        "                      seq=32, steps=2)\n"
        "sys.exit(0 if ok else 1)\n"
    )
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(ROOT, "src"), ROOT])
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=580)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    out = r.stdout
    assert out.count("bit-exact OK") == 8
    assert out.count("demand=uniform") == 4
    assert out.count("[selftest] moe_comm") == 2 and "FAIL" not in out
    assert "mesh={'data': 1, 'model': 4}" in out
    assert "expert_shard_devices=4 OK" in out
