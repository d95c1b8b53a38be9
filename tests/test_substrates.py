"""Optimizer, data pipeline, checkpointing, sharding specs, policies."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.checkpoint import ckpt
from repro.configs import ARCH_IDS, get_config
from repro.core.cost import CostModel, ResourceModel
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.optim import adamw
from repro.sharding.context import SINGLE, ParallelContext


# --------------------------------------------------------------------------- #
# AdamW
# --------------------------------------------------------------------------- #


def test_adamw_matches_manual_reference():
    """One update on a toy param vs hand-computed AdamW math."""
    cfg = adamw.AdamWConfig(lr=0.1, beta1=0.9, beta2=0.99, eps=1e-8,
                            weight_decay=0.01, clip_norm=1e9,
                            warmup_steps=0, total_steps=1, min_lr_frac=1.0)
    p = {"w": jnp.array([1.0, -2.0])}
    g = {"w": jnp.array([0.5, 0.5])}
    st_ = adamw.init(p)
    p2, st2, _ = adamw.update(cfg, p, g, st_)
    m = 0.1 * 0.5
    v = 0.01 * 0.25
    mh = m / (1 - 0.9)
    vh = v / (1 - 0.99)
    step = 0.1 * (mh / (np.sqrt(vh) + 1e-8) + 0.01 * np.array([1.0, -2.0]))
    np.testing.assert_allclose(np.asarray(p2["w"]),
                               np.array([1.0, -2.0]) - step, rtol=1e-6)
    assert int(st2.step) == 1


def test_adamw_converges_on_quadratic():
    cfg = adamw.AdamWConfig(lr=0.05, weight_decay=0.0, warmup_steps=0,
                            total_steps=200)
    p = {"w": jnp.array([5.0, -3.0])}
    s = adamw.init(p)
    for _ in range(200):
        g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(p)
        p, s, _ = adamw.update(cfg, p, g, s)
    assert float(jnp.abs(p["w"]).max()) < 0.1


def test_grad_clipping():
    g = {"w": jnp.full((4,), 100.0)}
    clipped, norm = adamw.clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(200.0)
    assert float(jnp.linalg.norm(clipped["w"])) == pytest.approx(1.0, rel=1e-5)


def test_schedule_shape():
    cfg = adamw.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                            min_lr_frac=0.1)
    lrs = [float(adamw.schedule(cfg, jnp.int32(s))) for s in range(100)]
    assert lrs[0] < lrs[9] <= 1.0            # warmup rises
    assert lrs[10] == pytest.approx(1.0, rel=1e-3)
    assert lrs[-1] < 0.2                      # cosine decays toward min
    assert min(lrs[10:]) >= 0.1 * 0.99


# --------------------------------------------------------------------------- #
# data pipeline
# --------------------------------------------------------------------------- #


def test_data_deterministic_and_sharded():
    base = dict(vocab=1000, seq_len=32, global_batch=8, seed=3)
    a = SyntheticLM(DataConfig(**base)).batch(7)
    b = SyntheticLM(DataConfig(**base)).batch(7)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    # shards partition the batch deterministically and differ
    s0 = SyntheticLM(DataConfig(**base, n_shards=2, shard=0)).batch(7)
    s1 = SyntheticLM(DataConfig(**base, n_shards=2, shard=1)).batch(7)
    assert s0["tokens"].shape == (4, 32)
    assert not np.array_equal(s0["tokens"], s1["tokens"])


def test_data_has_learnable_structure():
    cfg = DataConfig(vocab=100, seq_len=4096, global_batch=2, seed=0,
                     ngram_repeat=0.5)
    b = SyntheticLM(cfg).batch(0)
    f = np.random.default_rng(0).permutation(100)
    hits = (f[b["tokens"][:, :-1]] == b["tokens"][:, 1:]).mean()
    assert hits > 0.4  # bigram rule fires ~ngram_repeat of the time


# --------------------------------------------------------------------------- #
# checkpointing
# --------------------------------------------------------------------------- #


def test_checkpoint_roundtrip(tmp_path):
    tree = {
        "params": {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
                   "blocks": [jnp.ones((2,)), jnp.zeros((3,), jnp.int32)]},
        "opt": adamw.init({"w": jnp.ones((4,))}),
    }
    d = ckpt.save(str(tmp_path), 42, tree)
    assert os.path.exists(os.path.join(d, "index.json"))
    restored, step = ckpt.restore(str(tmp_path),
                                  namedtuple_types={"OptState": adamw.OptState})
    assert step == 42
    assert isinstance(restored["opt"], adamw.OptState)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_latest(tmp_path):
    ckpt.save(str(tmp_path), 1, {"x": jnp.ones(2)})
    ckpt.save(str(tmp_path), 5, {"x": jnp.zeros(2)})
    restored, step = ckpt.restore(str(tmp_path))
    assert step == 5
    np.testing.assert_array_equal(np.asarray(restored["x"]), np.zeros(2))


# --------------------------------------------------------------------------- #
# sharding specs (validity across ALL archs x production mesh geometry)
# --------------------------------------------------------------------------- #


class _FakeMesh:
    axis_names = ("pod", "data", "model")
    devices = np.empty((2, 16, 16))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_specs_divisible(arch):
    from repro.models.registry import build_model
    from repro.sharding.specs import build_param_specs

    ctx = ParallelContext(mesh=_FakeMesh(), data_axes=("pod", "data"))
    cfg = get_config(arch)
    model = build_model(cfg, ctx)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = build_param_specs(params, ctx)
    sizes = {"pod": 2, "data": 16, "model": 16}

    def check(path, leaf, spec):
        for dim, axis in zip(leaf.shape, tuple(spec) + (None,) * 8):
            if axis is None:
                continue
            axes = axis if isinstance(axis, tuple) else (axis,)
            total = int(np.prod([sizes[a] for a in axes]))
            assert dim % total == 0, (path, leaf.shape, spec)

    jax.tree_util.tree_map_with_path(
        lambda p, l, s: check(p, l, s), params, specs
    )


def test_moe_experts_sharded_over_model():
    from repro.models.registry import build_model
    from repro.sharding.specs import build_param_specs

    ctx = ParallelContext(mesh=_FakeMesh(), data_axes=("pod", "data"))
    cfg = get_config("qwen3-moe-235b-a22b")
    model = build_model(cfg, ctx)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = build_param_specs(params, ctx)
    assert tuple(specs["blocks"]["wg"])[1] == "model"  # [L, E, D, F]


# --------------------------------------------------------------------------- #
# paper policies (§IV-B, §V-B)
# --------------------------------------------------------------------------- #


def test_hysteresis_smoothing():
    cm = CostModel(hysteresis=0.5)
    from repro.core.topology import Topology
    rm = ResourceModel(Topology(4, 4), cm)
    prev = np.ones(rm.n_resources)
    now = np.zeros(rm.n_resources)
    sm = rm.smooth_loads(prev, now)
    np.testing.assert_allclose(sm, 0.5)


def test_relay_path_cost_infinite_below_threshold():
    from repro.core.paths import enumerate_paths
    from repro.core.topology import Topology
    t = Topology(4, 4)
    rm = ResourceModel(t, CostModel(split_threshold=1 << 20))
    costs = rm.resource_cost(np.zeros(rm.n_resources))
    relay = [p for p in enumerate_paths(t, 0, 1) if p.n_relays][0]
    assert rm.path_cost(relay, costs, 0.5 * (1 << 20)) == float("inf")
    assert rm.path_cost(relay, costs, 4 * (1 << 20)) < float("inf")
