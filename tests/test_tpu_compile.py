"""Compiles of the main path's kernels and of EP dispatch/combine for a
described (not attached) TPU v5e:2x2, at real widths.

Nothing runs: each test asserts that the TPU compiler accepted the program
and, where a Pallas kernel belongs, that it is in it (``tpu_custom_call``).
The topology is described inside a fixture, never at import time, because
only one process at a time may load the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels import tpu_kernels
from repro.kernels.flash_attention.flash import flash_attention
from repro.kernels.flash_attention.ops import attention
from repro.kernels.grouped_ffn.ffn import grouped_ffn_blocked, row_tile
from repro.models import moe
from repro.sharding.context import ParallelContext

GRANITE = "granite-moe-1b-a400m"


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _grouped_ffn_compile(one_chip, n, d, f, e, dtype):
    """The kernel for ``n`` routed rows, at the row tile it picks for them
    and the padded buffer ``grouped_ffn`` hands it."""
    s = lambda shape, t: jax.ShapeDtypeStruct(shape, t, sharding=one_chip)
    bf = 128
    bt = row_tile(n, e, d, bf, jnp.dtype(dtype).itemsize,
                  jnp.dtype(dtype).itemsize)
    m = (-(-n // bt) + e) * bt

    def fn(x, blk, rows, wg, wu, wd):
        return grouped_ffn_blocked(x, blk, rows, wg, wu, wd, block_tokens=bt,
                                   block_ffn=bf, interpret=False)

    compiled = jax.jit(fn).lower(
        s((m, d), dtype), s((m // bt,), jnp.int32), s((m // bt,), jnp.int32),
        s((e, d, f), dtype), s((e, d, f), dtype), s((e, f, d), dtype),
    ).compile()
    return bt, compiled


def test_grouped_ffn_granite_widths(one_chip):
    cfg = get_config(GRANITE)
    _, compiled = _grouped_ffn_compile(          # S=2048 forward
        one_chip, 2048 * cfg.top_k, cfg.d_model, cfg.d_ff, cfg.n_experts,
        jnp.float32)
    assert "grouped_ffn" in tpu_kernels(compiled.as_text())


def test_grouped_ffn_paper_block_f32(one_chip):
    """§V-D block (d=4096, F=16384, E=8) with f32 weights, 8192 routed rows
    (4096 tokens, top-2): the 512-row tiles the kernel picks fit its scoped
    VMEM, which is more than the compiler's default."""
    cfg = get_config("paper-moe-8e")
    bt, compiled = _grouped_ffn_compile(one_chip, 8192, cfg.d_model,
                                        cfg.d_ff, cfg.n_experts, jnp.float32)
    assert bt == 512
    assert "grouped_ffn" in tpu_kernels(compiled.as_text())


def test_flash_attention_granite_widths(one_chip):
    cfg = get_config(GRANITE)
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                           sharding=one_chip)
    S, dh = 2048, cfg.head_dim

    def fn(q, k, v):
        return flash_attention(q, k, v, causal=True, window=cfg.window,
                               interpret=False)

    compiled = jax.jit(fn).lower(
        s((1, cfg.n_heads, S, dh)), s((1, cfg.n_kv_heads, S, dh)),
        s((1, cfg.n_kv_heads, S, dh))).compile()
    assert "flash_attention" in tpu_kernels(compiled.as_text())


def test_flash_attention_moonlight_widths(one_chip):
    """Latent attention's widths in bf16 (keys 192 wide, values 128), causal
    over 2 x 4096 positions: the grid of live block pairs and its two
    scalar-prefetched tables compile for the chip."""
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                           sharding=one_chip)

    def fn(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    compiled = jax.jit(fn).lower(
        s((2, 16, 4096, 192)), s((2, 16, 4096, 192)),
        s((2, 16, 4096, 128))).compile()
    assert "flash_attention" in tpu_kernels(compiled.as_text())


@pytest.mark.parametrize("batch", [1, 4])
def test_flash_attention_on_four_chip_mesh(topo, monkeypatch, batch):
    """Under a (data=1, model=4) mesh the kernel runs per shard, split over
    heads (batch 1) or over the batch (batch 4): XLA cannot partition a
    Mosaic kernel itself."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config(GRANITE)
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    S, dh = 2048, cfg.head_dim
    s = lambda h: jax.ShapeDtypeStruct((batch, h, S, dh), jnp.float32,
                                       sharding=NamedSharding(mesh, P()))
    with jax.set_mesh(mesh):
        compiled = jax.jit(
            lambda q, k, v: attention(q, k, v, window=cfg.window)
        ).lower(s(cfg.n_heads), s(cfg.n_kv_heads), s(cfg.n_kv_heads)).compile()
    assert "flash_attention" in tpu_kernels(compiled.as_text())


def test_flash_attention_refuses_an_axis_it_cannot_split(topo, monkeypatch):
    """A model axis of 4 divides neither a batch of 1 nor 2 kv heads: the
    kernel is not silently repeated on every device."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    s = jax.ShapeDtypeStruct((1, 2, 128, 64), jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    with jax.set_mesh(mesh), pytest.raises(ValueError, match="divides"):
        jax.jit(lambda q, k, v: attention(q, k, v)).lower(s, s, s)


@pytest.fixture(scope="module")
def ep_compiled(topo):
    """Granite's EP expert layer (nimble dispatch -> grouped_ffn -> combine)
    on a (data=1, model=4) mesh of the described chips, compiled."""
    cfg = get_config(GRANITE)
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    ctx = ParallelContext(mesh=mesh, ep_size=4, group_size=2,
                          moe_mode="nimble")
    E, d, F = cfg.n_experts, cfg.d_model, cfg.d_ff

    def s(shape, spec):
        return jax.ShapeDtypeStruct(shape, jnp.float32,
                                    sharding=NamedSharding(mesh, spec))

    ex = P("model", None, None)
    p = {"router": s((d, E), P()), "wg": s((E, d, F), ex),
         "wu": s((E, d, F), ex), "wd": s((E, F, d), ex)}
    x = s((4, 256, d), P(None, "model", None))
    # the expert layer picks its kernel by backend; this process's is the CPU
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        apply = moe.make_moe_ffn(cfg, ctx)
        return jax.jit(apply).lower(p, x).compile()


def test_moe_dispatch_combine_four_chips(ep_compiled):
    """Granite's EP expert layer (nimble dispatch -> grouped_ffn -> combine)
    on a (data=1, model=4) mesh of the described chips."""
    text = ep_compiled.as_text()
    assert "grouped_ffn" in tpu_kernels(text)
    assert "collective-permute" in text        # the NIMBLE rounds
    assert ep_compiled.memory_analysis() is not None


def test_moe_four_chips_scopes(ep_compiled):
    """The stages' named scopes reach the TPU compiler's output, where the
    trace reduction reads them: every exchange hop is in ``nimble.rounds``,
    the counts' all-gather in ``nimble.plan``, the kernel in ``nimble.ffn``
    and the send buffer's sort and scatter in ``nimble.pack``."""
    from bench import scopes    # the trace reduction's reading of HLO text

    ops = [(name, op_name or "") for _, name, op_name, _, _ in
           scopes.instructions(ep_compiled.as_text())]
    hops = [n for name, n in ops if name.startswith("collective-permute")]
    assert hops and all(scopes.scope_of(n) == "nimble.rounds"
                        for n in hops), hops
    assert any(n.endswith("nimble.plan/all_gather") for _, n in ops)
    ffn = [n for name, n in ops if name.startswith("grouped_ffn")]
    assert ffn and all(scopes.scope_of(n) == "nimble.ffn" for n in ffn), ffn
    assert any(scopes.scope_of(n) == "nimble.pack" for name, n in ops
               if name.split(".")[0] in ("sort", "scatter"))


def test_moe_one_chip_scopes(one_chip, monkeypatch):
    """On one chip at the paper's block widths (d 4096, 8 experts of width
    16384, top-2, 4096 tokens), the router, the FFN and the combine carry
    their scopes."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("paper-moe-8e")
    E, d, F = cfg.n_experts, cfg.d_model, cfg.d_ff
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                           sharding=one_chip)
    p = {"router": s((d, E)), "wg": s((E, d, F)), "wu": s((E, d, F)),
         "wd": s((E, F, d))}
    apply = moe.make_moe_ffn(cfg, ParallelContext())
    text = jax.jit(lambda p, x: apply(p, x[None])[0]).lower(
        p, s((4096, d))).compile().as_text()
    from bench import scopes    # the trace reduction's reading of HLO text

    names = [n for _, _, n, _, _ in scopes.instructions(text) if n]
    found = {scopes.scope_of(n) for n in names}
    for scope in ("nimble.route", "nimble.ffn", "nimble.combine"):
        assert scope in found, scope
    assert any(n.endswith("nimble.ffn/jit(grouped_ffn_blocked)/grouped_ffn/"
                          "pallas_call") for n in names)


def test_moonlight_moe_layer_and_attention(one_chip, monkeypatch):
    """Moonlight's MoE layer (64 experts of width 1408, top-6, the sigmoid
    router, 2 shared experts) and its latent attention, bf16 at published
    widths over 2 x 4096 tokens: 49152 routed rows give 256-row tiles
    (bm <= m / 2E = 384), whose VMEM is well inside the kernel's limit."""
    from repro.kernels.grouped_ffn.ffn import VMEM_LIMIT_BYTES, _vmem_bytes
    from repro.models import layers

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("moonlight-16b-a3b")
    E, d, F, bf16 = cfg.n_experts, cfg.d_model, cfg.d_ff, jnp.bfloat16
    rows = 8192 * cfg.top_k
    bm = row_tile(rows, E, d, 128, 2, 2)
    assert bm == 256
    assert _vmem_bytes(bm, d, 128, 2, 2) < VMEM_LIMIT_BYTES // 4
    s = lambda shape, t=bf16: jax.ShapeDtypeStruct(shape, t,
                                                   sharding=one_chip)
    Fs = cfg.n_shared_experts * F
    p = {"router": s((d, E)), "router_bias": s((E,), jnp.float32),
         "wg": s((E, d, F)), "wu": s((E, d, F)), "wd": s((E, F, d)),
         "shared": {"wg": s((d, Fs)), "wu": s((d, Fs)), "wd": s((Fs, d))}}
    apply = moe.make_moe_ffn(cfg, ParallelContext(param_dtype=bf16,
                                                  compute_dtype=bf16))
    text = jax.jit(lambda p, x: apply(p, x)[0]).lower(
        p, s((2, 4096, d))).compile().as_text()
    assert "grouped_ffn" in tpu_kernels(text)

    attn = jax.tree.map(lambda a: s(a.shape),
                        jax.eval_shape(lambda: layers.init_mla(
                            jax.random.key(0), cfg, bf16)))
    text = jax.jit(lambda p, x: layers.mla_forward(p, x, cfg)).lower(
        attn, s((2, 4096, d))).compile().as_text()
    assert "flash_attention" in tpu_kernels(text)
