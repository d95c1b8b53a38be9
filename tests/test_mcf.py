"""Algorithm 1 (MWU min-congestion MCF) — correctness + properties."""

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.cost import CostModel
from repro.core.mcf import (
    congestion_lower_bound,
    solve_direct,
    solve_mwu,
    solve_static_striping,
)
from repro.core.paths import DIRECT, all_pairs_paths, enumerate_paths
from repro.core.topology import Topology

MB = 1 << 20


def paper_topo():
    return Topology(8, group_size=4)


# --------------------------------------------------------------------------- #
# path enumeration (paper §IV-B candidate families)
# --------------------------------------------------------------------------- #


def test_intra_candidates():
    t = paper_topo()
    paths = enumerate_paths(t, 0, 1)
    assert len(paths) == 3  # direct + 2 two-hop (G-2 intermediates)
    assert paths[0].family == DIRECT and paths[0].n_hops == 1
    for p in paths[1:]:
        assert p.n_hops == 2 and p.n_relays == 1


def test_inter_candidates_rail_matched():
    t = paper_topo()
    paths = enumerate_paths(t, 1, 5)
    assert len(paths) == 4  # one per rail
    # every path crosses exactly one rail link
    for p in paths:
        rails = [l for l in p.links if t.kind[l] != 0]
        assert len(rails) == 1
    # least-hop candidate first (1 hop: same rail both sides)
    assert paths[0].n_hops == 1


def test_paths_connect_endpoints():
    t = paper_topo()
    for (s, d), paths in all_pairs_paths(t).items():
        for p in paths:
            assert p.nodes[0] == s and p.nodes[-1] == d
            for a, b in zip(p.nodes, p.nodes[1:]):
                assert t.has_link(a, b)


# --------------------------------------------------------------------------- #
# Algorithm 1 invariants
# --------------------------------------------------------------------------- #


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 32 - 1))
def test_all_demand_routed(seed):
    rng = np.random.default_rng(seed)
    t = paper_topo()
    D = {}
    for s in range(8):
        for d in range(8):
            if s != d and rng.random() < 0.5:
                D[(s, d)] = float(rng.integers(1, 64)) * MB
    if not D:
        return
    plan = solve_mwu(t, D, eps=1 * MB)
    routed = plan.per_pair_bytes()
    for k, v in D.items():
        assert routed[k] == pytest.approx(v, rel=1e-9)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2 ** 32 - 1), st.floats(0.0, 0.95))
def test_nimble_no_worse_than_direct(seed, hot):
    """Min-max congestion of the MWU plan <= static direct plan."""
    rng = np.random.default_rng(seed)
    t = paper_topo()
    per = 64 * MB
    D = {}
    for s in range(8):
        for d in range(8):
            if s == d:
                continue
            D[(s, d)] = per * hot if d == 0 else per * (1 - hot) / 6
    nim = solve_mwu(t, D, eps=1 * MB)
    direct = solve_direct(t, D)
    assert nim.max_normalized_load() <= direct.max_normalized_load() * 1.02


def test_lower_bound_holds():
    t = paper_topo()
    rng = np.random.default_rng(0)
    D = {(s, d): float(rng.integers(1, 128)) * MB
         for s in range(8) for d in range(8) if s != d}
    nim = solve_mwu(t, D, eps=1 * MB)
    lb = congestion_lower_bound(t, D)
    assert nim.max_normalized_load() >= lb * 0.999
    # and the approximation is decent (within 1.5x of the cut bound)
    assert nim.max_normalized_load() <= lb * 1.5


def test_small_message_stays_single_path():
    """Paper policy: <=1 MB never splits onto relay paths (Fig. 6c)."""
    t = Topology(4, group_size=4)
    plan = solve_mwu(t, {(0, 1): 1 * MB}, eps=256 * 1024)
    assert plan.n_paths_used((0, 1)) == 1
    assert all(f.path.n_relays == 0 for f in plan.flows[(0, 1)])


def test_large_message_splits():
    t = Topology(4, group_size=4)
    plan = solve_mwu(t, {(0, 1): 256 * MB}, eps=1 * MB)
    assert plan.n_paths_used((0, 1)) == 3  # direct + both relays


def test_deterministic():
    t = paper_topo()
    D = {(s, d): float((s * 7 + d) % 5 + 1) * MB * 8
         for s in range(8) for d in range(8) if s != d}
    a = solve_mwu(t, D, eps=1 * MB)
    b = solve_mwu(t, D, eps=1 * MB)
    assert np.array_equal(a.resource_bytes, b.resource_bytes)


def test_striping_between_direct_and_nimble_under_skew():
    t = paper_topo()
    per = 64 * MB
    D = {}
    for s in range(8):
        for d in range(8):
            if s == d:
                continue
            D[(s, d)] = per * 0.8 if d == 0 else per * 0.2 / 6
    zd = solve_direct(t, D).max_normalized_load()
    zs = solve_static_striping(t, D).max_normalized_load()
    zn = solve_mwu(t, D, eps=1 * MB).max_normalized_load()
    assert zn <= zs * 1.05
    assert zs <= zd
