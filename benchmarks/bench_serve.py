"""Serving control plane — continuous-traffic SLO drills (DESIGN.md §10).

Each section replays one built-in registry scenario through *both*
control-plane arms (`repro.serve.ControlPlane`): the adaptive arm runs
every tenant as an arbitrated ``Session`` on a shared congestion-pricing
fabric, the static arm freezes each tenant's plan at join — and the
scenario's declared :class:`~repro.serve.SloSpec` gates the pair:

  * **steady** — two balanced tenants, no drills.  The no-regression
    scenario: the adaptive control plane must *match* the static baseline
    (combined drain parity >= 0.99x) while holding every SLO;
  * **elephant_victim** — a victim tenant absorbing sustained background
    elephant flows on a rail pair.  Adaptive re-solves must spread the
    elephant across alternates (combined drain win > 1x) while holding
    the Jain floor and the p99 gate;
  * **flap_under_load** — drifting skew while a rail link flaps.
    Adaptive must beat static on combined drain, recover within the SLO's
    window budget after the final restore, and keep availability up;
  * **churn** — ``churn_storm``'s scavenger storm against the same
    scenario with churn stripped: once the last churned tenant leaves,
    the survivor's steady-state (tail-median) drain must sit within 2% of
    the never-churned run, and churn must never cost the survivor more
    than 2% over the whole horizon.

Metrics land in ``BENCH_serve.json`` (tagged ``nimble.serve/v1``, the
adaptive arm's full per-scenario reports embedded);
:func:`validate_serve` is the ``run.py --smoke`` ``serve_slo`` gate.
"""

from __future__ import annotations

import numpy as np

from repro.serve import (
    evaluate_scenario,
    get_scenario,
    run_scenario,
    validate_serve_record,
)

from .common import emit


def _gate_values(slo: dict) -> dict:
    return {k: v["value"] for k, v in slo["gates"].items()}


def _scenario_section(name: str) -> dict:
    """Both arms + SLO verdict for one registry scenario, summarized."""
    spec = get_scenario(name)
    res = evaluate_scenario(spec)
    adaptive, static, slo = res["adaptive"], res["static"], res["slo"]
    win = slo["gates"]["combined_drain"]["value"]
    emit(
        f"serve/{name}/W{spec.windows}", 0.0,
        f"slo={'PASS' if slo['pass'] else 'FAIL'} win={win:.3f}x "
        f"jain={adaptive.jain_index:.3f} avail={adaptive.availability:.3f} "
        f"tenants={len(adaptive.tenants)}",
    )
    return {
        "windows": spec.windows,
        "tenants": len(adaptive.tenants),
        "slo_pass": bool(slo["pass"]),
        "gates": _gate_values(slo),
        "adaptive_total_s": adaptive.total_completion_s,
        "static_total_s": static.total_completion_s,
        "win": float(win),
        "jain": float(adaptive.jain_index),
        "availability": float(adaptive.availability),
        "recovery_windows": adaptive.recovery_windows,
        "fault_digest": adaptive.fault_digest,
        "report": adaptive.to_json_obj(),
    }


def churn_section() -> dict:
    """``churn_storm`` vs its never-churned control, same adaptive arm."""
    spec = get_scenario("churn_storm")
    churned = run_scenario(spec, "adaptive")
    control = run_scenario(spec.without_churn(), "adaptive")
    survivor = spec.tenants[0].name
    last_leave = max(
        t.leave_window for t in spec.roster() if t.leave_window is not None
    )
    vc = churned.tenants[survivor].ring.values()
    v0 = control.tenants[survivor].ring.values()
    tail_c = float(np.median(vc[last_leave:]))
    tail_0 = float(np.median(v0[last_leave:]))
    tail_ratio = tail_c / tail_0 if tail_0 > 0 else 1.0
    total_ratio = (
        churned.tenants[survivor].completion_s
        / control.tenants[survivor].completion_s
    )
    churners = [
        n for n, led in churned.tenants.items() if n != survivor
    ]
    emit(
        f"serve/churn/W{spec.windows}", 0.0,
        f"survivor_tail={tail_ratio:.4f}x control (target |r-1| <= 0.02) "
        f"whole_run={total_ratio:.4f}x churners={len(churners)}",
    )
    return {
        "windows": spec.windows,
        "survivor": survivor,
        "churned_tenants": len(churners),
        "last_leave_window": int(last_leave),
        "survivor_tail_s": tail_c,
        "control_tail_s": tail_0,
        "tail_ratio": float(tail_ratio),
        "total_ratio": float(total_ratio),
    }


# -- smoke gate -------------------------------------------------------------------

def validate_serve(metrics: dict) -> None:
    """The ``serve_slo`` gate (``run.py --smoke``).

    Raises ``ValueError`` naming the first violated invariant:

      * every scenario section holds its declared SLOs (the scenario's own
        ``SloSpec`` — p99, availability, Jain, recovery, drain floors);
      * steady: adaptive/static combined-drain parity >= 0.99x;
      * elephant_victim and flap_under_load: adaptive strictly beats
        static on combined drain (> 1.0x);
      * churn: survivor steady-state tail within 2% of the never-churned
        control, whole-run drain no more than 2% worse;
      * each embedded report is a valid ``nimble.serve/v1`` record.
    """
    for key in ("steady", "elephant_victim", "flap_under_load", "churn"):
        if key not in metrics or not isinstance(metrics[key], dict):
            raise ValueError(f"serve metrics missing section {key!r}")
    for name in ("steady", "elephant_victim", "flap_under_load"):
        sec = metrics[name]
        if not sec["slo_pass"]:
            failed = [
                g for g, v in sec["gates"].items()
                if isinstance(v, (int, float)) and not np.isfinite(v)
            ]
            raise ValueError(
                f"serve scenario {name!r}: SLO gates failed "
                f"(gates: {sec['gates']})"
                + (f"; non-finite: {failed}" if failed else "")
            )
        validate_serve_record(sec["report"])
    if metrics["steady"]["win"] < 0.99:
        raise ValueError(
            f"serve steady: adaptive parity {metrics['steady']['win']:.4f}x "
            "static < 0.99x — the adaptive control plane regresses a "
            "scenario it should match"
        )
    for name in ("elephant_victim", "flap_under_load"):
        if metrics[name]["win"] <= 1.0:
            raise ValueError(
                f"serve {name}: adaptive {metrics[name]['win']:.4f}x static "
                "— no combined-drain win on a skewed scenario"
            )
    churn = metrics["churn"]
    if abs(churn["tail_ratio"] - 1.0) > 0.02:
        raise ValueError(
            f"serve churn: survivor tail {churn['tail_ratio']:.4f}x the "
            "never-churned control (threshold 2%)"
        )
    if churn["total_ratio"] > 1.02:
        raise ValueError(
            f"serve churn: survivor whole-run drain {churn['total_ratio']:.4f}"
            "x the never-churned control — churn cost more than 2%"
        )


def metrics() -> dict:
    return {
        "steady": _scenario_section("steady"),
        "elephant_victim": _scenario_section("elephant_victim"),
        "flap_under_load": _scenario_section("flap_under_load"),
        "churn": churn_section(),
    }


def run() -> dict:
    return metrics()


def smoke() -> dict:
    """CI variant — host numpy over n=8; all four drills run in seconds."""
    return metrics()


if __name__ == "__main__":
    run()
