"""Fault injection + graceful degradation contracts (DESIGN.md §9).

Pins the robustness surface of ISSUE 6:

  * injector determinism — same (seed, spec, topology) compiles to a
    bit-identical schedule (``FaultSchedule.digest``), property-tested;
  * EventLog flap semantics — down→restore in one window, duplicate
    downs, restore-scheduled-before-down: schedule order wins;
  * telemetry guard — NaN/negative load records rejected whole, counted;
  * estimator degraded mode — last-good prediction under blackout with
    decaying confidence, NaN back-fill, clean-window reset;
  * policy flap backoff — replan storms suppressed geometrically,
    deferred catch-up, quiet-period reset, opt-out;
  * runtime watchdog — a pending plan stuck past its deadline is
    abandoned exactly once and re-solved against live demand;
  * planner degraded mode — the sweep solver prices candidates off down
    links; ``solve_degraded`` routes every pair on survivors;
  * fabric teardown — withdraw/unregister idempotent under racing
    teardown paths, staleness eviction fires exactly once;
  * the ``validate_faults`` bench gate rejects threshold violations.
"""

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import CostModel, ResourceModel, solve_degraded, solve_mwu
from repro.core.topology import DOWN_CAP, Topology
from repro.faults import (
    ElephantFlowSpec,
    FaultInjector,
    FaultScenario,
    LinkFlapSpec,
    RailLossSpec,
    StragglerSpec,
    TelemetryBlackoutSpec,
    TenantCrashSpec,
)
from repro.fabric import ArbiterConfig, FabricArbiter, FabricState
from repro.runtime import (
    DemandEstimator,
    EventLog,
    LinkTelemetry,
    OrchestrationRuntime,
    PolicyConfig,
    ReplanPolicy,
    RuntimeConfig,
    balanced_trace,
    link_down,
    link_restored,
)
from repro.runtime.events import merge_overrides

MB = 1 << 20
N = 8
G = 4


@pytest.fixture(scope="module")
def topo():
    return Topology(N, group_size=G)


# -- injector determinism (satellite 5) -----------------------------------------

def _scenario(seed, start, cycles, jitter, drop):
    return FaultScenario(
        name="prop",
        seed=seed,
        flaps=(LinkFlapSpec(0, G, start=start, cycles=cycles,
                            down_windows=2, up_windows=2, jitter=jitter),),
        blackouts=(TelemetryBlackoutSpec(start=start + 1, duration=4,
                                         drop_prob=drop),),
        stragglers=(StragglerSpec(start=start, duration=3, inflation=2.5),),
        elephants=(ElephantFlowSpec(1, G + 1, start=start, duration=6,
                                    bytes_per_window=64 * MB, jitter=0.3),),
        crashes=(TenantCrashSpec("B", window=start + 5),),
    )


@settings(max_examples=20, deadline=None)
@given(
    st.integers(0, 2**16),
    st.integers(0, 8),
    st.integers(1, 4),
    st.floats(0.0, 0.8),
    st.floats(0.1, 0.9),
)
def test_same_seed_same_schedule(seed, start, cycles, jitter, drop):
    """Two injectors, same (seed, spec, topo) -> bit-identical digests."""
    t = Topology(N, group_size=G)
    spec = _scenario(seed, start, cycles, jitter, drop)
    a = FaultInjector(t).compile(spec)
    b = FaultInjector(t).compile(spec)
    assert a.digest() == b.digest()
    assert a.events == b.events
    for w, mask in a.dropout_masks.items():
        assert np.array_equal(mask, b.dropout_masks[w])
    # expansion invariants hold under any jitter: events window-sorted,
    # every restore lands after its down, cycles never interleave
    windows = [ev.window for ev in a.events]
    assert windows == sorted(windows)
    prev_restore = None
    for dn, up in zip(a.events[::2], a.events[1::2]):
        assert dn.scale == 0.0 and up.scale == 1.0
        assert up.window == dn.window + spec.flaps[0].down_windows
        if prev_restore is not None:
            assert dn.window >= prev_restore
        prev_restore = up.window


def test_different_seed_different_masks(topo):
    a = FaultInjector(topo).compile(_scenario(1, 4, 2, 0.5, 0.5))
    b = FaultInjector(topo).compile(_scenario(2, 4, 2, 0.5, 0.5))
    assert a.digest() != b.digest()


def test_injector_validates_topology(topo):
    inj = FaultInjector(topo)
    with pytest.raises(ValueError):
        inj.compile(FaultScenario(
            name="bad", flaps=(LinkFlapSpec(0, N + 3, start=0),)
        ))
    with pytest.raises(ValueError):
        inj.compile(FaultScenario(
            name="bad", rail_losses=(RailLossSpec(device=N + 1, start=0),)
        ))


def test_rail_loss_fans_out_to_nic_links(topo):
    sched = FaultInjector(topo).compile(FaultScenario(
        name="rail",
        rail_losses=(RailLossSpec(device=0, start=3, restore=7),),
    ))
    downs = [ev for ev in sched.events if ev.scale == 0.0]
    ups = [ev for ev in sched.events if ev.scale == 1.0]
    assert len(downs) == len(ups) >= 1
    assert all(ev.window == 3 and 0 in (ev.src, ev.dst) for ev in downs)
    assert all(ev.window == 7 for ev in ups)


# -- EventLog flap sequences (satellite 2) --------------------------------------

def test_down_then_restore_same_window_restore_wins():
    log = EventLog([link_down(3, 0, G), link_restored(3, 0, G)])
    due = log.pop_due(3)
    assert [ev.scale for ev in due] == [0.0, 1.0]
    assert merge_overrides(due) == [((0, G), 1.0)]


def test_restore_scheduled_before_down_down_wins():
    # schedule order wins, not scale order: the restore was scheduled
    # first, so the later down is the final word for the window
    log = EventLog()
    log.schedule(link_restored(3, 0, G))
    log.schedule(link_down(3, 0, G))
    assert merge_overrides(log.pop_due(3)) == [((0, G), 0.0)]


def test_duplicate_downs_collapse():
    log = EventLog([link_down(2, 0, G), link_down(2, 0, G)])
    assert merge_overrides(log.pop_due(2)) == [((0, G), 0.0)]


def test_pop_due_orders_across_windows():
    log = EventLog([link_restored(5, 0, G), link_down(2, 0, G)])
    assert [ev.window for ev in log.pop_due(10)] == [2, 5]


def test_runtime_same_window_flap_leaves_fabric_healthy(topo):
    """A down+restore pair landing in one window must not degrade links."""
    log = EventLog([link_down(1, 0, G), link_restored(1, 0, G)])
    rt = OrchestrationRuntime(topo, events=log)
    for d in balanced_trace(N, 4):
        rt.step(d)
    assert rt.topo.down_link_ids() == []


# -- telemetry guard (satellite 3) ----------------------------------------------

def test_record_loads_rejects_poison(topo):
    cap = ResourceModel(topo).capacity
    tel = LinkTelemetry(cap)
    good = cap * 1e-3
    tel.record_loads(0, good)
    assert len(tel) == 1

    nan_loads = good.copy()
    nan_loads[0] = np.nan
    tel.record_loads(1, nan_loads)
    neg_loads = good.copy()
    neg_loads[0] = -1.0
    tel.record_loads(2, neg_loads)
    inf_loads = good.copy()
    inf_loads[0] = np.inf
    tel.record_loads(3, inf_loads)

    assert len(tel) == 1            # poisoned records dropped whole
    assert tel.rejected == 3
    agg = tel.aggregate()
    assert agg["rejected_records"] == 3
    assert np.isfinite(tel.mean_util()).all()

    # a shape mismatch is a caller bug, not producer corruption
    with pytest.raises(ValueError):
        tel.record_loads(4, good[:-1])
    assert tel.rejected == 3


# -- estimator degraded mode ----------------------------------------------------

def test_estimator_blackout_serves_last_good():
    est = DemandEstimator(4)
    d = np.zeros((4, 4))
    d[0, 1] = 100 * MB
    est.update(d)
    est.update(d)
    before = est.predict().copy()
    assert est.confidence == 1.0

    est.update(None)
    assert np.array_equal(est.predict(), before)   # last-good held
    assert est.confidence == pytest.approx(0.5)
    assert est.missing_windows == 1
    est.update(None)
    assert est.confidence == pytest.approx(0.25)

    est.update(d)                                  # clean window resets
    assert est.confidence == 1.0
    assert np.isfinite(est.predict()).all()


def test_estimator_partial_dropout_backfills():
    est = DemandEstimator(4)
    d = np.full((4, 4), 10.0 * MB)
    np.fill_diagonal(d, 0.0)
    est.update(d)
    obs = d.copy()
    obs[0, 1] = np.nan
    est.update(obs)
    assert np.isfinite(est.predict()).all()        # NaN never leaks out
    assert 0.5 < est.confidence < 1.0              # partial, not blackout


# -- policy flap backoff --------------------------------------------------------

def _topo_decide(pol, w, event=True):
    return pol.decide(window=w, ratio=1.0, baseline_ratio=1.0,
                      plan_age=0, pending=False, topology_event=event)


def test_flap_backoff_suppresses_storm():
    pol = ReplanPolicy(PolicyConfig())
    reasons = [_topo_decide(pol, w).reason for w in range(8)]
    # geometric spacing: fires at w0, w1, w3, w7 — the rest suppressed
    assert reasons == ["topology", "topology", "backoff", "topology",
                       "backoff", "backoff", "backoff", "topology"]


def test_flap_backoff_deferred_catchup_fires_once():
    pol = ReplanPolicy(PolicyConfig())
    assert _topo_decide(pol, 0).replan
    assert _topo_decide(pol, 1).replan
    assert _topo_decide(pol, 2).reason == "backoff"   # suppressed, deferred
    catchup = _topo_decide(pol, 3, event=False)
    assert catchup.replan and catchup.reason == "topology"
    # the deferred flag is consumed: nothing else fires spontaneously
    assert not _topo_decide(pol, 4, event=False).replan


def test_flap_backoff_quiet_period_resets_level():
    cfg = PolicyConfig()
    pol = ReplanPolicy(cfg)
    for w in range(4):
        _topo_decide(pol, w)                          # escalate to level 2
    quiet = 3 + cfg.flap_reset_windows + 1
    assert _topo_decide(pol, quiet).reason == "topology"
    # level reset to 0 -> backoff is base again, so the very next window
    # fires instead of being blocked by the escalated horizon
    assert _topo_decide(pol, quiet + 1).reason == "topology"


def test_flap_backoff_disabled_fires_every_event():
    pol = ReplanPolicy(PolicyConfig(flap_backoff_base=0))
    assert all(_topo_decide(pol, w).reason == "topology" for w in range(6))


# -- runtime watchdog -----------------------------------------------------------

def test_watchdog_abandons_stuck_pending(topo):
    # replan latency (12) far beyond the pending deadline (4): the plan
    # issued for the w2 link-down goes stale in flight and the watchdog
    # abandons it exactly once, re-solving against live demand
    rt = OrchestrationRuntime(
        topo,
        cfg=RuntimeConfig(solve_delay_windows=12, pending_deadline_windows=4),
        events=EventLog([link_down(2, 0, G)]),
    )
    reports = [rt.step(d) for d in balanced_trace(N, 24)]
    assert rt.stats.watchdog_abandons == 1      # watchdog pending is exempt
    assert any(r.plan_source == "watchdog" and r.swapped for r in reports)
    assert all(np.isfinite(r.completion_s) for r in reports)


def test_watchdog_disabled_never_fires(topo):
    rt = OrchestrationRuntime(
        topo,
        cfg=RuntimeConfig(solve_delay_windows=12,
                          pending_deadline_windows=None),
        events=EventLog([link_down(2, 0, G)]),
    )
    for d in balanced_trace(N, 24):
        rt.step(d)
    assert rt.stats.watchdog_abandons == 0


# -- planner degraded mode ------------------------------------------------------

def test_sweep_solver_avoids_down_link(topo):
    down = topo.with_link_scale({(0, G): 0.0})
    lid = down.link_id(0, G)
    assert lid in down.down_link_ids()
    plan = solve_mwu(down, {(0, G): 256 * MB}, refresh="sweep")
    assert not plan.degraded                     # MWU converged on survivors
    assert plan.link_bytes[lid] == 0.0           # nothing priced onto the stub
    assert plan.per_pair_bytes()[(0, G)] == pytest.approx(256 * MB, rel=1e-9)


def test_healthy_solve_not_degraded(topo):
    plan = solve_mwu(topo, {(0, G): 64 * MB, (1, G + 1): 64 * MB})
    assert not plan.degraded


def test_solve_degraded_routes_everything(topo):
    down = topo.with_link_scale({(0, G): 0.0, (1, G + 1): 0.0})
    demands = {(0, G): 128 * MB, (1, G + 1): 64 * MB, (2, G + 2): 32 * MB}
    plan = solve_degraded(down, demands)
    assert plan.degraded
    routed = plan.per_pair_bytes()
    for key, d in demands.items():
        assert routed[key] == pytest.approx(d, rel=1e-9)
    # survivors exist for every pair on this fabric, so no payload
    # touches a down link
    for lid in down.down_link_ids():
        assert plan.link_bytes[lid] == 0.0


# -- fabric teardown + eviction (satellite 1) -----------------------------------

def test_withdraw_unknown_tenant_is_noop(topo):
    state = FabricState(topo)
    state.withdraw("ghost")                      # must not raise
    R = state.rm.n_resources
    state.commit("a", np.ones(R))
    state.withdraw("a")
    state.withdraw("a")                          # double withdraw: no-op
    assert state.committed_load("a") is None


def test_unregister_idempotent(topo):
    arb = FabricArbiter(topo)
    arb.register("a")
    arb.unregister("a")
    arb.unregister("a")                          # racing teardown: no-op
    arb.unregister("ghost")
    assert arb.tenants() == []


def test_staleness_eviction_fires_once(topo):
    arb = FabricArbiter(topo, cfg=ArbiterConfig(evict_staleness=3.0))
    arb.register("a")
    arb.register("b")
    R = arb.state.rm.n_resources
    loads = np.full(R, float(MB))
    arb.commit("a", loads, window=0)
    arb.commit("b", loads, window=0)
    for w in range(1, 5):                        # "b" stops heartbeating
        arb.commit("a", loads, window=w)
    assert arb.stats.evictions == 1
    assert arb.tenants() == ["a"]
    assert arb.state.committed_load("b") is None  # load withdrawn with it
    arb.unregister("b")                          # late session close: no-op
    assert arb.stats.evictions == 1


def test_eviction_disabled_by_default(topo):
    arb = FabricArbiter(topo)
    arb.register("a")
    arb.register("b")
    R = arb.state.rm.n_resources
    arb.commit("b", np.ones(R), window=0)
    for w in range(1, 50):
        arb.commit("a", np.ones(R), window=w)
    assert arb.tenants() == ["a", "b"]
    assert arb.stats.evictions == 0


# -- drill harness + bench gate -------------------------------------------------

def test_schedule_consumption_helpers(topo):
    sched = FaultInjector(topo).compile(FaultScenario(
        name="mix",
        seed=3,
        blackouts=(TelemetryBlackoutSpec(start=2, duration=2, drop_prob=1.0),
                   TelemetryBlackoutSpec(start=6, duration=2, drop_prob=0.4)),
        stragglers=(StragglerSpec(start=4, duration=1, inflation=3.0),),
        elephants=(ElephantFlowSpec(0, G, start=1, duration=2,
                                    bytes_per_window=8 * MB),),
        crashes=(TenantCrashSpec("B", window=5),),
    ))
    d = np.zeros((N, N))
    assert sched.observed_demand(2, d) is None            # full blackout
    partial = sched.observed_demand(6, d)
    assert partial is not None and np.isnan(partial).any()
    assert sched.observed_demand(0, d) is d               # untouched window
    assert sched.perturbed_demand(1, d)[0, G] >= 8 * MB * 0.5
    assert sched.completion_scale(4) == 3.0
    assert sched.completion_scale(0) == 1.0
    assert not sched.crashed("B", 4) and sched.crashed("B", 5)
    assert sched.horizon >= 7


def test_validate_faults_gate_rejects_regressions():
    from benchmarks.bench_faults import validate_faults

    good = {
        "flap": {"recovery_windows": 0, "flap_events": 8,
                 "topology_replans_backoff": 4, "topology_replans_storm": 8,
                 "availability": 1.0},
        "blackout": {"adaptive_static_ratio": 0.9, "missing_windows": 8,
                     "blackout_windows": 8, "availability": 1.0},
        "tenant_crash": {"evictions": 1, "survivor_solo_ratio": 1.0,
                         "double_teardown_ok": True},
        "perturb": {"telemetry_rejected": 0, "straggler_ratio": 3.0},
    }
    validate_faults(good)                                 # healthy: no raise

    import copy
    for section, key, bad in [
        ("flap", "recovery_windows", 5),
        ("flap", "recovery_windows", None),
        ("flap", "topology_replans_backoff", 9),
        ("flap", "availability", 0.5),
        ("blackout", "adaptive_static_ratio", 1.2),
        ("blackout", "missing_windows", 3),
        ("tenant_crash", "evictions", 0),
        ("tenant_crash", "survivor_solo_ratio", 1.5),
        ("tenant_crash", "double_teardown_ok", False),
        ("perturb", "telemetry_rejected", 2),
        ("perturb", "straggler_ratio", 1.0),
    ]:
        broken = copy.deepcopy(good)
        broken[section][key] = bad
        with pytest.raises(ValueError):
            validate_faults(broken)
    with pytest.raises(ValueError):
        validate_faults({k: v for k, v in good.items() if k != "blackout"})
