"""Scenario registry + serving control plane (DESIGN.md §10).

Pins the declarative layer's contracts:

  * every built-in scenario survives ``to_json -> from_json`` bit-exactly
    (dataclass-equal specs *and* byte-identical re-serialization);
  * unknown keys raise ``ValueError`` naming the offending key, at every
    nesting level (scenario, topology, tenant, traffic, churn, faults,
    slo) — a typo'd scenario file must fail loudly, not drop a gate;
  * traffic programs and ``compile_churn`` are deterministic pure
    functions of their seeds (hypothesis-property pinned, with the
    fixed-sample fallback when hypothesis is absent);
  * the control plane serves the roster for the full horizon in both
    arms, replays bit-identically, and exports a valid ``nimble.serve/v1``
    record; ``evaluate_slo`` gates behave as documented.

Runs are bounded: n=8 fabric, horizons <= 20 windows.
"""

import dataclasses

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.serve import (
    BUILTIN_SCENARIOS,
    ChurnSpec,
    ControlPlane,
    ScenarioSpec,
    SloSpec,
    TenantSpec,
    TrafficProgram,
    compile_churn,
    evaluate_scenario,
    evaluate_slo,
    get_scenario,
    load_scenario,
    run_scenario,
    scenario_names,
    validate_serve_record,
)

MB = float(1 << 20)


def _two_tenant(windows=8, **slo_kw):
    return ScenarioSpec(
        name="t",
        topology=get_scenario("minimal").topology,
        windows=windows,
        tenants=(
            TenantSpec("a", TrafficProgram("steady", seed=1)),
            TenantSpec("b", TrafficProgram("steady", bytes_per_src=128 * MB,
                                           seed=2), qos="scavenger"),
        ),
        slo=SloSpec(**slo_kw),
    )


# -- registry round trip ----------------------------------------------------------

@pytest.mark.serve
@pytest.mark.parametrize("name", sorted(BUILTIN_SCENARIOS))
def test_builtin_round_trips_bit_exact(name):
    spec = get_scenario(name)
    obj = spec.to_json_obj()
    assert obj["schema"] == "nimble.serve_scenario/v1"
    back = ScenarioSpec.from_json_obj(obj)
    assert back == spec
    # and the byte form is a fixed point
    data = spec.to_json()
    again = ScenarioSpec.from_json(data)
    assert again == spec
    assert again.to_json() == data


@pytest.mark.serve
def test_registry_surface():
    assert scenario_names() == sorted(BUILTIN_SCENARIOS)
    assert {"steady", "diurnal", "churn_storm", "flap_under_load",
            "elephant_victim", "minimal"} <= set(BUILTIN_SCENARIOS)
    with pytest.raises(ValueError, match="unknown scenario"):
        get_scenario("nope")
    # fresh spec per call — registry state can't be mutated by callers
    assert get_scenario("steady") is not get_scenario("steady")


@pytest.mark.serve
def test_load_scenario_from_file(tmp_path):
    spec = get_scenario("flap_under_load")
    path = tmp_path / "scn.json"
    path.write_bytes(spec.to_json())
    assert load_scenario(str(path)) == spec
    with pytest.raises(ValueError, match="neither a built-in"):
        load_scenario(str(tmp_path / "missing.json"))


@pytest.mark.serve
@pytest.mark.parametrize("mutate,expect", [
    (lambda o: o.__setitem__("turbo", 1), r"scenario: unknown key 'turbo'"),
    (lambda o: o["topology"].__setitem__("n_racks", 2),
     r"scenario\.topology: unknown key 'n_racks'"),
    (lambda o: o["tenants"][0].__setitem__("priority", 9),
     r"tenant 'web': unknown key 'priority'"),
    (lambda o: o["tenants"][0]["traffic"].__setitem__("burst", 2),
     r"tenant 'web'\.traffic: unknown key 'burst'"),
    (lambda o: o["slo"].__setitem__("p50_latency_s", 1.0),
     r"scenario\.slo: unknown key 'p50_latency_s'"),
])
def test_unknown_keys_raise_naming_offender(mutate, expect):
    obj = get_scenario("steady").to_json_obj()
    mutate(obj)
    with pytest.raises(ValueError, match=expect):
        ScenarioSpec.from_json_obj(obj)


@pytest.mark.serve
def test_unknown_keys_in_churn_and_faults():
    obj = get_scenario("churn_storm").to_json_obj()
    obj["churn"]["burstiness"] = 3
    with pytest.raises(ValueError, match=r"churn: unknown key 'burstiness'"):
        ScenarioSpec.from_json_obj(obj)

    obj = get_scenario("flap_under_load").to_json_obj()
    obj["faults"]["meteors"] = []
    with pytest.raises(ValueError, match=r"faults: unknown key 'meteors'"):
        ScenarioSpec.from_json_obj(obj)

    obj = get_scenario("flap_under_load").to_json_obj()
    obj["faults"]["flaps"][0]["severity"] = 2
    with pytest.raises(
        ValueError, match=r"faults\.flaps\[0\]: unknown key 'severity'"
    ):
        ScenarioSpec.from_json_obj(obj)


@pytest.mark.serve
def test_spec_validation_rejects_bad_values():
    with pytest.raises(ValueError, match="unknown traffic kind"):
        TrafficProgram("bursty")
    with pytest.raises(ValueError, match="leave_window"):
        TenantSpec("x", TrafficProgram("steady"), join_window=5,
                   leave_window=5)
    with pytest.raises(ValueError, match="at least one tenant"):
        _two_tenant().__class__(
            name="empty", topology=get_scenario("minimal").topology,
            windows=4, tenants=(),
        )
    with pytest.raises(ValueError, match="duplicate tenant name"):
        dataclasses.replace(
            _two_tenant(),
            tenants=(
                TenantSpec("a", TrafficProgram("steady")),
                TenantSpec("a", TrafficProgram("steady", seed=9)),
            ),
        )


# -- determinism ------------------------------------------------------------------

@pytest.mark.serve
def test_traffic_is_stateless_in_window():
    """demand(w) depends on (seed, w) only — no generator state, so a
    late joiner sees exactly the traffic it would always have seen."""
    for kind in ("steady", "diurnal", "drift", "flips"):
        prog = TrafficProgram(kind, seed=5)
        fresh = prog.demand(7, 8)
        for w in (0, 3, 11, 7):
            again = prog.demand(w, 8)
            assert again.shape == (8, 8)
            assert float(np.diag(again).sum()) == 0.0
            assert (again >= 0).all()
        np.testing.assert_array_equal(prog.demand(7, 8), fresh)


@pytest.mark.serve
def test_diurnal_swells_and_phase_shifts():
    prog = TrafficProgram("diurnal", hot=0, period=12, swell=2.0,
                          jitter=0.0, seed=0)
    trough, peak = prog.demand(0, 8), prog.demand(6, 8)
    assert peak.sum() > 1.9 * trough.sum()          # swell at mid-period
    assert peak[1:, 0].sum() > 0.6 * peak[1:].sum()  # concentrated on hot
    shifted = TrafficProgram("diurnal", hot=0, period=12, swell=2.0,
                             jitter=0.0, phase=6, seed=0)
    np.testing.assert_array_equal(shifted.demand(0, 8), peak)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 6), st.integers(1, 5), st.integers(1, 4),
       st.integers(0, 2), st.integers(0, 2 ** 16), st.integers(6, 40))
@pytest.mark.serve
def test_churn_compiles_deterministically(n_tenants, lifetime, spacing,
                                          jitter, seed, windows):
    """Property: compile_churn is a pure function of (spec, windows), its
    tenants respect the lifetime/ordering invariants, and a longer
    horizon only extends the schedule prefix."""
    spec = ChurnSpec(
        template=TrafficProgram("steady", bytes_per_src=32 * MB),
        n_tenants=n_tenants, lifetime=lifetime, spacing=spacing,
        jitter=jitter, seed=seed,
    )
    a = compile_churn(spec, windows)
    b = compile_churn(spec, windows)
    assert a == b
    assert len({t.name for t in a}) == len(a)  # slot-indexed unique names
    for t in a:
        assert t.qos == "scavenger"
        assert 0 <= t.join_window < windows - 1
        assert t.leave_window > t.join_window
    longer = compile_churn(spec, windows + 10)
    assert longer[: len(a)] == a


@pytest.mark.serve
def test_scenario_roster_and_without_churn():
    spec = get_scenario("churn_storm")
    roster = spec.roster()
    assert roster == spec.roster()  # deterministic
    churned = [t for t in roster if t.name.startswith("churn-")]
    assert len(churned) >= 3
    control = spec.without_churn()
    assert control.churn is None
    assert control.roster() == spec.tenants
    assert control.windows == spec.windows


# -- control plane ----------------------------------------------------------------

@pytest.mark.serve
@pytest.mark.timeout(120)
def test_control_plane_serves_full_roster_both_arms():
    spec = _two_tenant(windows=8)
    for mode in ("adaptive", "static"):
        rep = run_scenario(spec, mode)
        assert rep.mode == mode
        assert set(rep.tenants) == {"a", "b"}
        for led in rep.tenants.values():
            assert led.windows == spec.windows
            assert led.completion_s > 0
            assert led.payload_bytes > 0
        assert len(rep.window_latency_s) == spec.windows
        assert min(rep.window_latency_s) > 0
        validate_serve_record(rep.to_json_obj())
    with pytest.raises(ValueError, match="unknown mode"):
        ControlPlane(spec, mode="oracle")


@pytest.mark.serve
@pytest.mark.timeout(120)
def test_control_plane_replays_bit_identically():
    spec = _two_tenant(windows=6)
    a = run_scenario(spec, "adaptive")
    b = run_scenario(spec, "adaptive")
    assert a.window_latency_s == b.window_latency_s
    for name in a.tenants:
        assert a.tenants[name].completion_s == b.tenants[name].completion_s
        assert a.tenants[name].replans == b.tenants[name].replans


@pytest.mark.serve
@pytest.mark.timeout(180)
def test_churned_tenants_spawn_and_retire():
    spec = dataclasses.replace(
        get_scenario("churn_storm"), windows=16,
        slo=SloSpec(jain_floor=0.0),
    )
    rep = run_scenario(spec, "adaptive")
    churned = {n: led for n, led in rep.tenants.items()
               if n.startswith("churn-")}
    assert churned, "no churned tenant entered the horizon"
    for t in spec.roster():
        led = rep.tenants[t.name]
        assert led.joined == t.join_window
        expect_left = (
            t.leave_window if t.leave_window is not None
            and t.leave_window <= spec.windows else spec.windows
        )
        assert led.left == expect_left
        assert led.windows == led.left - led.joined


@pytest.mark.serve
@pytest.mark.timeout(180)
def test_evaluate_scenario_minimal_passes_slo():
    res = evaluate_scenario(get_scenario("minimal"))
    assert res["slo"]["pass"], res["slo"]["gates"]
    gates = res["slo"]["gates"]
    assert {"p99_latency", "availability", "jain", "combined_drain",
            "tenant_drain"} <= set(gates)
    for g in gates.values():
        assert set(g) == {"ok", "value", "limit"}


@pytest.mark.serve
@pytest.mark.timeout(120)
def test_evaluate_slo_gate_semantics():
    rep = run_scenario(_two_tenant(windows=6), "adaptive")
    # no baseline: drain gates are skipped, latency/fairness still judged
    solo = evaluate_slo(rep, SloSpec())
    assert "combined_drain" not in solo["gates"]
    assert "tenant_drain" not in solo["gates"]
    assert "recovery" not in solo["gates"]
    # recovery gate appears only when budgeted; no fault events -> fails
    budgeted = evaluate_slo(rep, SloSpec(max_recovery_windows=2))
    assert budgeted["gates"]["recovery"]["value"] is None
    assert not budgeted["gates"]["recovery"]["ok"]
    # an impossible jain floor flips the verdict
    strict = evaluate_slo(rep, SloSpec(jain_floor=1.0))
    assert strict["gates"]["jain"]["ok"] == (rep.jain_index >= 1.0)


@pytest.mark.serve
def test_validate_serve_record_names_violation():
    rec = run_scenario(get_scenario("minimal"), "static").to_json_obj()
    validate_serve_record(rec)
    bad = dict(rec)
    bad["schema"] = "nimble.other/v1"
    with pytest.raises(ValueError, match="nimble.serve"):
        validate_serve_record(bad)
    bad = dict(rec)
    bad["cluster"] = dict(rec["cluster"], availability=1.5)
    with pytest.raises(ValueError, match="availability"):
        validate_serve_record(bad)
    bad = dict(rec)
    bad.pop("tenants")
    with pytest.raises(ValueError, match="tenants"):
        validate_serve_record(bad)
