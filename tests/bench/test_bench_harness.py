"""The harness's window: sampling from the seed, the percentile, and the
count of compiles inside the timed window."""

import statistics

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness


def test_reservoir_is_seeded_and_uniform():
    def draw(seed):
        r = harness.Reservoir(3, seed)
        for i in range(50):
            r.offer(i, i)
        return sorted(i for i, _ in r.items)

    assert draw(2147483701) == draw(2147483701)
    hits = np.zeros(50)
    for seed in range(400):
        hits[draw(seed)] += 1
    # each call is kept with probability 3/50: 24 of 400 draws on average
    assert hits.sum() == 1200 and hits.min() > 5 and hits.max() < 50


def test_p95_is_the_statistics_percentile():
    v = list(np.random.default_rng(0).random(200))
    assert harness.p95(v) == statistics.quantiles(v, n=100,
                                                  method="inclusive")[94]
    assert harness.p95([0.5]) == 0.5


class _Step:
    def __init__(self, fresh_each_call: bool):
        self.fresh = fresh_each_call
        self.f = jax.jit(lambda x: x * 2)
        self.f(jnp.ones(3)).block_until_ready()

    def step(self, i):
        if self.fresh:           # a new shape: a compile inside the window
            return jax.jit(lambda x: x + i)(jnp.ones(i + 1))
        return self.f(jnp.ones(3))


@pytest.mark.parametrize("fresh,compiles", [(False, False), (True, True)])
def test_window_counts_compiles(fresh, compiles):
    counter = harness.CompileCounter()
    win = harness.run_window(_Step(fresh), 0.05, harness.Reservoir(1, 0),
                             counter)
    assert win.calls >= 1 and win.seconds >= 0.05
    assert (win.compiles > 0) is compiles
