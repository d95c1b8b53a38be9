"""Price recency (ISSUE 5): decayed ledger prices, swap-boundary
re-pricing, fingerprint-checked commits, and the unregister hint audit.

The contracts under test:

  * window-stamped commits drive a fabric clock; a stamped peer's exported
    price fades with a configurable half-life (monotone non-increasing in
    staleness — property-tested), unstamped host commits never fade, and
    ``price_decay=None`` is byte-identical to the raw pre-recency ledger
    (the skew-vs-elephant acceptance scenario is pinned bit-exact);
  * a pending plan whose prices moved past ``price_hint_rel`` between
    issue and swap boundary still swaps, but is immediately re-solved
    against live prices (swap-and-refine, one round per replan chain);
  * the mutual-drift scenario that regressed to ~0.92x combined drain
    under raw prices holds >= 1.0x vs the unpriced baseline under the
    calibrated ``SessionSpec`` defaults;
  * ``FabricState.commit`` names both fingerprints when a tenant exports
    telemetry solved against a different fabric geometry, and accepts
    transient per-link-scale divergence;
  * ``FabricArbiter.unregister`` removes the departing tenant's bus
    subscription *before* the withdrawal hint and publishes nothing (and
    counts nothing) when no subscriber remains.
"""

import os
import sys

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.mcf import solve_direct, solve_mwu
from repro.core.topology import Topology
from repro.fabric import (
    ArbiterConfig,
    FabricArbiter,
    FabricState,
    RepriceDecision,
)
from repro.runtime import (
    OrchestrationRuntime,
    PolicyConfig,
    PricesMovedHint,
    ReplanPolicy,
    balanced_trace,
)

ROOT = os.path.join(os.path.dirname(__file__), "..")

MB = float(1 << 20)
N = 8
G = 4


@pytest.fixture(scope="module")
def topo():
    return Topology(N, group_size=G)


def skew_demand(bytes_per_src=64 * MB, hot=0, hot_frac=0.7):
    return {
        (s, d): bytes_per_src * (
            hot_frac if d == hot else (1.0 - hot_frac) / (N - 2)
        )
        for s in range(N)
        for d in range(N)
        if s != d
    }


def elephant(topo, mb=128.0, rails=(0, 1)):
    D = {}
    for r in rails:
        D[(r, r + G)] = mb * MB
        D[(r + G, r)] = mb * MB
    return solve_direct(topo, D)


# -- ledger recency ---------------------------------------------------------------

def test_commit_stamps_and_clock(topo):
    state = FabricState(topo)
    loads = np.ones(state.n_resources)
    state.commit("host", loads)                 # unstamped
    state.commit("rt", loads, window=3)         # stamped
    assert state.clock == 3
    assert state.staleness("host") is None
    assert state.staleness("rt") == 0.0
    state.commit("rt2", loads, window=7)
    assert state.clock == 7
    assert state.staleness("rt") == 4.0
    # a commit stamped behind the clock never rewinds it
    state.commit("rt", loads, window=5)
    assert state.clock == 7 and state.staleness("rt") == 2.0
    # withdrawal forgets the stamp
    state.withdraw("rt")
    assert state.staleness("rt") is None


def test_decay_factor_semantics(topo):
    state = FabricState(topo)
    loads = np.ones(state.n_resources)
    state.commit("host", loads)
    state.commit("stale", loads, window=0)
    state.commit("fresh", loads, window=4)
    # half-life semantics: exactly halved per half_life windows of staleness
    assert state.decay_factor("stale", 4.0) == pytest.approx(0.5)
    assert state.decay_factor("stale", 2.0) == pytest.approx(0.25)
    # fresh, unstamped, unknown, and disabled half-lives are all exactly 1
    assert state.decay_factor("fresh", 2.0) == 1.0
    assert state.decay_factor("host", 2.0) == 1.0
    assert state.decay_factor("missing", 2.0) == 1.0
    assert state.decay_factor("stale", None) == 1.0
    assert state.decay_factor("stale", 0.0) == 1.0


def test_external_load_decay_none_bit_identical(topo):
    """half_life=None takes the exact raw-ledger path (total minus own)."""
    rng = np.random.default_rng(0)
    state = FabricState(topo)
    for i, t in enumerate(("a", "b", "c")):
        state.commit(t, rng.uniform(0.0, 1e9, state.n_resources), window=i)
    raw = state.external_load("a")
    expect = state.total_load() - state.committed_load("a")
    assert np.array_equal(raw, np.maximum(expect, 0.0))
    # with every entry unstamped the decayed path multiplies by exactly
    # 1.0 per peer — same value up to summation order (it sums peers
    # directly instead of total-minus-own)
    state2 = FabricState(topo)
    for t in ("a", "b", "c"):
        state2.commit(t, state.committed_load(t))  # unstamped
    decayed = state2.external_load("a", half_life=2.0)
    assert np.allclose(decayed, state2.external_load("a"), rtol=1e-15)
    assert np.array_equal(
        decayed,
        state2.committed_load("b") + state2.committed_load("c"),
    )


@settings(max_examples=20, deadline=None)
@given(st.floats(0.5, 16.0), st.integers(1, 6))
def test_decayed_prices_monotone_in_staleness(half_life, steps):
    """Property: a peer's decayed price is monotone non-increasing as the
    fabric clock runs past its last stamp."""
    topo = Topology(N, group_size=G)
    state = FabricState(topo)
    rng = np.random.default_rng(42)
    peer_load = rng.uniform(0.0, 1e9, state.n_resources)
    state.commit("peer", peer_load, window=0)
    state.commit("me", np.zeros(state.n_resources), window=0)
    prev = state.external_load("me", half_life=half_life)
    assert np.array_equal(prev, peer_load)  # staleness 0: exact
    for k in range(1, steps + 1):
        state.commit("me", np.zeros(state.n_resources), window=k)
        cur = state.external_load("me", half_life=half_life)
        assert (cur <= prev + 1e-9).all(), (
            f"decayed price increased with staleness at clock {k}"
        )
        assert (cur[peer_load > 0] < prev[peer_load > 0]).all()
        prev = cur


def test_prices_for_applies_decay(topo):
    bg = elephant(topo).resource_bytes
    arb = FabricArbiter(topo, cfg=ArbiterConfig(price_decay=2.0))
    raw = FabricArbiter(topo)
    for a in (arb, raw):
        a.register("me")
        a.register("peer")
        a.commit("peer", bg, window=0)
        a.commit("me", np.zeros(a.state.n_resources), window=4)
    assert np.allclose(arb.prices_for("me"), 0.25 * bg)
    assert np.array_equal(raw.prices_for("me"), bg)  # price_decay=None raw


# -- regression: skew-vs-elephant pinned bit-identical under decay=None ----------

def test_skew_vs_elephant_bit_identical_decay_none(topo):
    """The PR-3 acceptance scenario byte-for-byte under price_decay=None —
    via the raw hand-wired arbiter and via the opt-out Session."""
    from repro.api import Session, SessionSpec

    D = skew_demand()
    bg = elephant(topo)

    # hand-wired raw-ledger reference (exactly the PR-3 code path)
    ref_arb = FabricArbiter(topo)
    ref_arb.register("skew")
    ref_arb.register("bg")
    ref_arb.commit("bg", bg.resource_bytes)
    ref = solve_mwu(topo, D, ext_loads=ref_arb.prices_for("skew"))
    ref_arb.commit("skew", ref.resource_bytes)

    spec = SessionSpec(topology=topo, adaptivity="arbitrated", tenant="skew",
                       price_decay=None, fabric_staleness=None)
    with Session(spec) as sess:
        sess.join_static_tenant("bg", bg)
        got = sess.plan(D)
        got_combined = sess.fabric.combined_drain_s()
    assert np.array_equal(got.resource_bytes, ref.resource_bytes)
    assert np.array_equal(got.link_bytes, ref.link_bytes)
    assert got.per_pair_bytes() == ref.per_pair_bytes()
    assert got_combined == ref_arb.combined_drain_s()
    # and the calibrated-default Session is *also* identical here: the
    # background commit is unstamped (timeless), so decay never touches it
    with Session(SessionSpec(topology=topo, adaptivity="arbitrated",
                             tenant="skew")) as sess:
        sess.join_static_tenant("bg", bg)
        assert np.array_equal(sess.plan(D).resource_bytes, ref.resource_bytes)


# -- swap-boundary re-pricing -----------------------------------------------------

def test_reprice_decision_semantics(topo):
    bg = elephant(topo).resource_bytes
    arb = FabricArbiter(topo)
    arb.register("me")
    arb.register("peer")
    # idle fabric, solved unpriced: nothing moved
    d = arb.reprice("me", None)
    assert isinstance(d, RepriceDecision)
    assert not d.moved and d.rel_change == 0.0 and d.prices is None
    # peer appears after the solve: full move
    arb.commit("peer", bg)
    d = arb.reprice("me", None)
    assert d.moved and d.rel_change == 1.0
    assert np.array_equal(d.prices, bg)
    # solved under the same prices: no move
    d = arb.reprice("me", bg.copy())
    assert not d.moved and d.rel_change == 0.0
    # sub-threshold wiggle: no move
    arb.commit("peer", bg * 1.05)
    assert not arb.reprice("me", bg.copy()).moved
    # peer withdrew after the solve: full move back to unpriced
    arb.state.withdraw("peer")
    d = arb.reprice("me", bg.copy())
    assert d.moved and d.prices is None
    assert arb.stats.reprices == 2  # only the moved verdicts count


def test_reprice_disabled_by_hint_rel_zero(topo):
    arb = FabricArbiter(topo, cfg=ArbiterConfig(price_hint_rel=0.0))
    arb.register("me")
    arb.register("peer")
    arb.commit("peer", elephant(topo).resource_bytes)
    d = arb.reprice("me", None)
    assert not d.moved and d.rel_change == 1.0  # measured, never acted on
    assert arb.stats.reprices == 0


def test_swap_boundary_reprices_stale_pending(topo):
    """A pending plan whose prices moved between issue and swap boundary
    swaps in AND spawns one re-priced refinement (swap-and-refine)."""
    trace = balanced_trace(N, 10)
    arb = FabricArbiter(topo)
    rt = OrchestrationRuntime(
        topo,
        policy=ReplanPolicy(PolicyConfig(max_staleness=3,
                                         cooldown_windows=0)),
    )
    arb.register_runtime("t", rt)
    arb.register("peer")

    reports = [rt.step(trace[0]), rt.step(trace[1]), rt.step(trace[2])]
    # w3 hits max_staleness: replan issued, solved under prices=None
    reports.append(rt.step(trace[3]))
    assert reports[-1].replan_issued and reports[-1].replan_reason == "staleness"
    # the fabric shifts while the plan is in flight
    arb.commit("peer", elephant(topo, mb=512.0).resource_bytes)
    # swap boundary: the admitted plan swaps, a refine is parked pending
    reports.append(rt.step(trace[4]))
    assert reports[-1].swapped
    assert rt.stats.reprices == 1 and arb.stats.reprices == 1
    # the refined (live-priced) plan lands at the next boundary
    reports.append(rt.step(trace[5]))
    assert reports[-1].swapped and reports[-1].plan_source == "reprice"
    # one refine round per chain: even with prices still moving, the
    # refined plan swapped without spawning another
    assert rt.stats.reprices == 1
    # refines complete an admitted replan — they are not new replans
    assert rt.stats.replans == 1


def test_reprice_skipped_when_prices_stable(topo):
    """Stable prices across the issue->swap window: swap exactly as the
    pre-recency runtime did, no refine, no extra solves."""
    trace = balanced_trace(N, 8)
    bg = elephant(topo)

    plain = OrchestrationRuntime(
        topo,
        policy=ReplanPolicy(PolicyConfig(max_staleness=3,
                                         cooldown_windows=0)),
    )
    arb = FabricArbiter(topo)
    rt = OrchestrationRuntime(
        topo,
        policy=ReplanPolicy(PolicyConfig(max_staleness=3,
                                         cooldown_windows=0)),
    )
    arb.register_runtime("t", rt)
    arb.register("peer")
    arb.commit("peer", bg.resource_bytes)   # committed BEFORE any solve
    res = rt.run_trace(trace)
    assert rt.stats.reprices == 0 and arb.stats.reprices == 0
    # same trigger cadence as an unpriced runtime (prices never moved)
    ref = plain.run_trace(trace)
    assert [r.replan_issued for r in res.reports] == [
        r.replan_issued for r in ref.reports
    ]
    assert [r.swapped for r in res.reports] == [
        r.swapped for r in ref.reports
    ]


# -- mutual drift: the headline acceptance ---------------------------------------

@pytest.mark.timeout(600)
def test_mutual_drift_calibrated_beats_unpriced():
    """ISSUE 5 acceptance: two mutually drifting arbitrated tenants under
    the calibrated recency defaults drain >= 1.0x vs the unpriced
    baseline (the raw-ledger arbiter regressed to ~0.92x), on the exact
    scenario the --smoke gate pins."""
    sys.path.insert(0, ROOT)
    try:
        from benchmarks.bench_fairness import (
            mutual_drift,
            validate_mutual_drift,
        )
    finally:
        sys.path.remove(ROOT)
    section = mutual_drift(windows=32)
    validate_mutual_drift(section)      # schema + win >= 1.0
    assert section["win"] >= 1.0, section["win"]
    assert section["win_legacy"] < 1.0, (
        "the raw-ledger regression disappeared — recalibrate the scenario"
    )
    assert section["arms"]["calibrated"]["reprices"] >= 1


# -- fingerprint-checked commits (satellite) --------------------------------------

def test_commit_rejects_foreign_geometry_fingerprint(topo):
    state = FabricState(topo)
    other = Topology(N, group_size=2)       # different geometry
    with pytest.raises(ValueError) as ei:
        state.commit(
            "t", np.ones(state.n_resources), fingerprint=other.fingerprint
        )
    msg = str(ei.value)
    assert str(other.fingerprint) in msg and str(state.fingerprint) in msg
    assert "t" in msg
    # the bare shape error still fires without a fingerprint, and points
    # at the fingerprint-naming path
    with pytest.raises(ValueError, match="shape"):
        state.commit("t", np.ones(3))


def test_commit_accepts_scale_only_divergence(topo):
    """A runtime mid-way through applying a broadcast link event commits
    with a scale-divergent fingerprint — expected, not an error."""
    state = FabricState(topo)
    state.apply_link_overrides({(0, G): 0.5})
    assert state.fingerprint != topo.fingerprint
    state.commit("t", np.ones(state.n_resources),
                 window=1, fingerprint=topo.fingerprint)
    assert state.tenants() == ["t"]


def test_arbiter_commit_passes_fingerprint_through(topo):
    arb = FabricArbiter(topo)
    arb.register("t")
    other = Topology(N, group_size=2)
    with pytest.raises(ValueError, match="fingerprint"):
        arb.commit("t", np.ones(arb.state.n_resources),
                   fingerprint=other.fingerprint)
    assert arb.stats.commits == 0   # rejected commits are not counted


def test_late_joiner_not_priced_stale(topo):
    """A tenant joining a fabric that already ran N windows starts its
    local window counter at 0; its commits must stamp in *fabric* windows
    (bind-time clock offset), or decay prices it to near-nothing and the
    incumbent plans as if it did not exist."""
    from repro.api import Session, SessionSpec

    trace = balanced_trace(N, 60)
    spec_a = SessionSpec(topology=topo, adaptivity="arbitrated", tenant="a")
    with Session(spec_a) as sa:
        for w in range(50):
            sa.step(trace[w])
        assert sa.fabric.state.clock == 49
        spec_b = SessionSpec(topology=topo, adaptivity="arbitrated",
                             tenant="b", fabric=sa.fabric)
        with Session(spec_b) as sb:
            sb.step(trace[50])
            # b's first commit is stamped at the fabric clock, not at 0
            assert sa.fabric.state.staleness("b") == 0.0
            decay = sa.fabric.cfg.price_decay
            assert sa.fabric.state.decay_factor("b", decay) == 1.0
            # a's prices therefore carry b's full committed load
            committed = sa.fabric.state.committed_load("b")
            assert np.array_equal(
                sa.fabric.prices_for("a"), committed
            )


def test_runtime_export_carries_window_and_fingerprint(topo):
    trace = balanced_trace(N, 3)
    arb = FabricArbiter(topo)
    rt = OrchestrationRuntime(topo)
    arb.register_runtime("t", rt)
    for w in range(3):
        rt.step(trace[w])
        assert arb.state.staleness("t") == 0.0
        assert arb.state.clock == w
    assert arb.stats.commits == 3


# -- unregister hint audit (satellite) --------------------------------------------

def test_unregister_no_hint_without_subscribers(topo):
    """The last runtime's own departure must not hint into the void: the
    bus is empty once it unsubscribes, so nothing is published and
    ``price_hints`` stays put."""
    arb = FabricArbiter(topo)
    rt = OrchestrationRuntime(topo)
    arb.register_runtime("solo", rt)
    arb.commit("solo", np.ones(arb.state.n_resources))
    before = arb.stats.price_hints
    arb.unregister("solo")
    assert arb.stats.price_hints == before
    assert len(arb.bus) == 0


def test_unregister_departing_tenant_never_sees_own_hint(topo):
    """Unsubscribe happens before the withdrawal hint: the survivor gets
    exactly one hint, the departing runtime's pressure clock stays off."""
    arb = FabricArbiter(topo)
    rt_leaving = OrchestrationRuntime(
        topo, policy=ReplanPolicy(PolicyConfig(fabric_staleness=1))
    )
    rt_staying = OrchestrationRuntime(
        topo, policy=ReplanPolicy(PolicyConfig(fabric_staleness=1))
    )
    arb.register_runtime("leaving", rt_leaving)
    arb.register_runtime("staying", rt_staying)
    loads = np.ones(arb.state.n_resources)
    arb.commit("leaving", loads)
    arb.commit("staying", loads)
    # isolate the withdrawal hint: clear the clocks the commit-path hints
    # legitimately started above
    rt_leaving.policy._pressure_window = None
    rt_staying.policy._pressure_window = None
    before = arb.stats.price_hints
    arb.unregister("leaving")
    assert arb.stats.price_hints == before + 1
    # the survivor's soft-staleness clock started; the departed runtime
    # was unsubscribed before the hint and never saw its own withdrawal
    assert rt_staying.policy._pressure_window is not None
    assert rt_leaving.policy._pressure_window is None


def test_unregister_hint_watermark_left_for_future_subscribers(topo):
    """A hint skipped for lack of subscribers must not consume the move:
    the next subscribed observer still sees the accumulated shift."""
    arb = FabricArbiter(topo)
    arb.register("a")
    arb.register("b")
    loads = np.ones(arb.state.n_resources)
    arb.commit("a", loads)      # no subscribers: skipped, watermark at 0
    arb.commit("b", loads)
    seen = []
    arb.bus.subscribe(lambda evs: seen.extend(evs))
    arb.commit("b", 1.05 * loads)  # tiny wiggle vs ledger, huge vs watermark
    hints = [e for e in seen if isinstance(e, PricesMovedHint)]
    assert len(hints) == 1 and hints[0].rel_change > 0.5
