"""Fault drills — recovery, availability, and replan discipline (DESIGN.md §9).

Each drill compiles a declarative :class:`repro.faults.FaultScenario`
against the bench fabric and replays a trace through the runtime under it,
measuring what graceful degradation actually bought:

  * **flap** — a link flap train (down/up cycles).  With flap backoff the
    topology replan count must stay bounded (vs. one replan per event for
    the no-backoff arm), and the fabric must recover to its pre-fault
    completion within two windows of the final restore;
  * **blackout** — a full telemetry blackout across a drift-phase change.
    The estimator serves last-good demand with decaying confidence; total
    adaptive completion must stay at or below the static one-shot
    baseline, with zero crashes;
  * **tenant_crash** — a co-tenant stops heartbeating mid-run on a shared
    arbitrated fabric.  Staleness eviction must fire, and the survivor's
    tail completion must land within 2% of a fabric the crashed tenant
    never joined.  Double teardown (evict, then session close) must be a
    no-op;
  * **perturb** — stragglers + background elephant + partial telemetry
    dropout composed on one run: the loop survives, straggler inflation is
    visible in the reports, and no telemetry record is rejected.

Metrics land in ``BENCH_faults.json`` (tagged ``nimble.bench_faults/v1``);
:func:`validate_faults` is the ``run.py --smoke`` gate (schema +
recovery/availability thresholds).
"""

from __future__ import annotations

import numpy as np

from repro.api import Session, SessionSpec
from repro.core.topology import Topology
from repro.fabric import ArbiterConfig, FabricArbiter
from repro.faults import (
    ElephantFlowSpec,
    FaultInjector,
    FaultScenario,
    LinkFlapSpec,
    StragglerSpec,
    TelemetryBlackoutSpec,
    TenantCrashSpec,
    run_drill,
)
from repro.runtime import PolicyConfig, balanced_trace, drifting_skew_trace

from .common import emit

N = 8
GROUP = 4
MB = 1 << 20


def _adaptive(topo, **kw) -> Session:
    return Session(SessionSpec(topology=topo, adaptivity="adaptive", **kw))


def flap_section(windows: int = 28, start: int = 8, cycles: int = 4) -> dict:
    """Flap train on one rail link; backoff arm vs. no-backoff arm."""
    topo = Topology(N, group_size=GROUP)
    trace = balanced_trace(N, windows)
    sched = FaultInjector(topo).compile(
        FaultScenario(
            name="flap",
            flaps=[
                LinkFlapSpec(
                    src=0, dst=GROUP, start=start, cycles=cycles,
                    down_windows=1, up_windows=1,
                )
            ],
        )
    )
    restore = max(ev.window for ev in sched.events)

    with _adaptive(topo) as sess:
        backoff = run_drill(sess, trace, sched)
    with _adaptive(topo, policy=PolicyConfig(flap_backoff_base=0)) as sess:
        storm = run_drill(sess, trace, sched)

    pre = backoff.healthy_median_s(start)
    rec = backoff.recovery_window(after=restore, threshold_s=1.5 * pre)
    topo_backoff = backoff.replans_by_reason().get("topology", 0)
    topo_storm = storm.replans_by_reason().get("topology", 0)
    avail = backoff.availability(pre)
    emit(
        f"faults/flap/W{windows}", 0.0,
        f"topo_replans={topo_backoff} (no-backoff {topo_storm}) "
        f"suppressed={len(backoff.backoff_windows)} "
        f"recovered@w{rec} (restore@w{restore}) avail={avail:.3f}",
    )
    return {
        "windows": windows,
        "digest": sched.digest(),
        "flap_events": len(sched.events),
        "restore_window": int(restore),
        "recovered_window": rec,
        "recovery_windows": (rec - restore) if rec is not None else None,
        "topology_replans_backoff": int(topo_backoff),
        "topology_replans_storm": int(topo_storm),
        "suppressed_windows": len(backoff.backoff_windows),
        "availability": float(avail),
        "prefault_completion_s": float(pre),
    }


def blackout_section(windows: int = 48, dwell: int = 12) -> dict:
    """Full telemetry blackout spanning a drift-phase change."""
    topo = Topology(N, group_size=GROUP)
    trace = drifting_skew_trace(N, windows, dwell=dwell)
    start, duration = 2 * dwell - 4, 8   # straddles the phase flip
    sched = FaultInjector(topo).compile(
        FaultScenario(
            name="blackout",
            blackouts=[TelemetryBlackoutSpec(start=start, duration=duration)],
        )
    )
    with Session(SessionSpec(topology=topo)) as static_sess:
        static = static_sess.run_trace(trace)
    with _adaptive(topo) as sess:
        drill = run_drill(sess, trace, sched)
        rt = sess.runtime
        missing = rt.estimator.missing_windows
        confidence = rt.estimator.confidence
    pre = drill.healthy_median_s(start)
    ratio = drill.total_completion_s / static.total_completion_s
    avail = drill.availability(pre)
    emit(
        f"faults/blackout/W{windows}", 0.0,
        f"adaptive/static={ratio:.3f} (target <= 1.0) "
        f"missing={missing}/{duration} conf_end={confidence:.3f} "
        f"avail={avail:.3f}",
    )
    return {
        "windows": windows,
        "digest": sched.digest(),
        "blackout_start": start,
        "blackout_windows": duration,
        "adaptive_completion_s": drill.total_completion_s,
        "static_completion_s": static.total_completion_s,
        "adaptive_static_ratio": float(ratio),
        "missing_windows": int(missing),
        "confidence_end": float(confidence),
        "availability": float(avail),
    }


def tenant_crash_section(
    windows: int = 36, dwell: int = 12, crash_at: int = 14
) -> dict:
    """Co-tenant crash on a shared fabric; staleness eviction + recovery."""
    topo = Topology(N, group_size=GROUP)
    trace = drifting_skew_trace(N, windows, dwell=dwell)
    tail = windows - 2 * dwell   # windows after the post-crash phase flip
    acfg = ArbiterConfig(price_decay=2.0, evict_staleness=6.0)
    sched = FaultInjector(topo).compile(
        FaultScenario(
            name="tenant_crash",
            crashes=[TenantCrashSpec(tenant="B", window=crash_at)],
        )
    )

    def tail_median(reports) -> float:
        return float(np.median([r.completion_s for r in reports[-tail:]]))

    # reference: the survivor on a fabric tenant B never joined
    with Session(SessionSpec(
        topology=topo, adaptivity="arbitrated", tenant="A",
        arbiter=acfg,
    )) as solo:
        solo_reports = [solo.step(trace[w]) for w in range(windows)]
    solo_tail = tail_median(solo_reports)

    arb = FabricArbiter(topo, cfg=acfg)
    sess_a = Session(SessionSpec(
        topology=topo, adaptivity="arbitrated", tenant="A", fabric=arb,
    ))
    sess_b = Session(SessionSpec(
        topology=topo, adaptivity="arbitrated", tenant="B", fabric=arb,
    ))
    a_reports = []
    for w in range(windows):
        a_reports.append(sess_a.step(trace[w]))
        if not sched.crashed("B", w):
            sess_b.step(trace[w])
    evictions = arb.stats.evictions
    survivors = arb.tenants()
    # double teardown: the crashed session's close runs *after* the
    # arbiter already evicted it — every sub-step must be a no-op
    sess_b.close()
    sess_b.close()
    arb.state.withdraw("B")          # withdraw of an unknown tenant: no-op
    double_teardown_ok = "B" not in arb.tenants() and "A" in arb.tenants()
    sess_a.close()

    crash_tail = tail_median(a_reports)
    ratio = crash_tail / solo_tail if solo_tail > 0 else 1.0
    emit(
        f"faults/tenant_crash/W{windows}", 0.0,
        f"survivor_tail/solo_tail={ratio:.4f} (target <= 1.02) "
        f"evictions={evictions} survivors={survivors}",
    )
    return {
        "windows": windows,
        "digest": sched.digest(),
        "crash_window": crash_at,
        "evictions": int(evictions),
        "survivors": survivors,
        "survivor_tail_s": crash_tail,
        "solo_tail_s": solo_tail,
        "survivor_solo_ratio": float(ratio),
        "double_teardown_ok": bool(double_teardown_ok),
    }


def perturb_section(windows: int = 20) -> dict:
    """Stragglers + background elephant + partial dropout, composed."""
    topo = Topology(N, group_size=GROUP)
    trace = balanced_trace(N, windows)
    sched = FaultInjector(topo).compile(
        FaultScenario(
            name="perturb",
            seed=7,
            stragglers=[StragglerSpec(start=8, duration=4, inflation=3.0)],
            elephants=[
                ElephantFlowSpec(
                    src=1, dst=GROUP + 1, start=4, duration=12,
                    bytes_per_window=256.0 * MB, jitter=0.2,
                )
            ],
            blackouts=[
                TelemetryBlackoutSpec(start=6, duration=8, drop_prob=0.3)
            ],
        )
    )
    with _adaptive(topo) as sess:
        drill = run_drill(sess, trace, sched)
        rejected = sess.runtime.telemetry.rejected
        confidence = sess.runtime.estimator.confidence
    comps = drill.completions()
    straggler_ratio = float(
        np.median(comps[8:12]) / max(np.median(comps[:8]), 1e-12)
    )
    emit(
        f"faults/perturb/W{windows}", 0.0,
        f"straggler_ratio={straggler_ratio:.2f} (inflation 3.0) "
        f"rejected={rejected} conf_end={confidence:.3f}",
    )
    return {
        "windows": windows,
        "digest": sched.digest(),
        "straggler_ratio": straggler_ratio,
        "telemetry_rejected": int(rejected),
        "confidence_end": float(confidence),
        "total_completion_s": drill.total_completion_s,
    }


# -- smoke gate -------------------------------------------------------------------

def validate_faults(metrics: dict) -> None:
    """Schema + threshold gate over the fault-drill metrics (``--smoke``).

    Raises ``ValueError`` naming the first violated invariant:

      * flap: recovery within <= 2 windows of the final restore, backoff
        replan count <= cycles + 1 and strictly bounded by the no-backoff
        arm, availability >= 0.75;
      * blackout: adaptive completion <= static baseline, every blackout
        window registered as missing, availability >= 0.9;
      * tenant_crash: exactly one eviction, survivor tail within 2% of the
        never-joined reference, double teardown a no-op;
      * perturb: zero rejected telemetry records, straggler inflation
        visible in the reports.
    """
    for key in ("flap", "blackout", "tenant_crash", "perturb"):
        if key not in metrics or not isinstance(metrics[key], dict):
            raise ValueError(f"fault metrics missing section {key!r}")
    flap = metrics["flap"]
    if flap["recovery_windows"] is None or flap["recovery_windows"] > 2:
        raise ValueError(
            f"flap drill: recovery took {flap['recovery_windows']} windows "
            "after the final restore (threshold 2)"
        )
    if flap["topology_replans_backoff"] > flap["topology_replans_storm"]:
        raise ValueError(
            "flap drill: backoff arm issued more topology replans "
            f"({flap['topology_replans_backoff']}) than the no-backoff arm "
            f"({flap['topology_replans_storm']})"
        )
    if flap["topology_replans_backoff"] > flap["flap_events"] // 2 + 1:
        raise ValueError(
            f"flap drill: {flap['topology_replans_backoff']} topology "
            f"replans for {flap['flap_events']} flap events — backoff cap "
            "not holding"
        )
    if flap["availability"] < 0.75:
        raise ValueError(
            f"flap drill: availability {flap['availability']:.3f} < 0.75"
        )
    blk = metrics["blackout"]
    if blk["adaptive_static_ratio"] > 1.0:
        raise ValueError(
            "blackout drill: adaptive completion "
            f"{blk['adaptive_static_ratio']:.3f}x static — last-good "
            "fallback lost to the one-shot baseline"
        )
    if blk["missing_windows"] < blk["blackout_windows"]:
        raise ValueError(
            f"blackout drill: estimator saw {blk['missing_windows']} "
            f"missing windows of {blk['blackout_windows']} blacked out"
        )
    if blk["availability"] < 0.9:
        raise ValueError(
            f"blackout drill: availability {blk['availability']:.3f} < 0.9"
        )
    crash = metrics["tenant_crash"]
    if crash["evictions"] != 1:
        raise ValueError(
            f"tenant-crash drill: {crash['evictions']} evictions, "
            "expected exactly 1"
        )
    if crash["survivor_solo_ratio"] > 1.02:
        raise ValueError(
            "tenant-crash drill: survivor tail "
            f"{crash['survivor_solo_ratio']:.4f}x the never-joined "
            "reference (threshold 1.02)"
        )
    if not crash["double_teardown_ok"]:
        raise ValueError("tenant-crash drill: double teardown not a no-op")
    pert = metrics["perturb"]
    if pert["telemetry_rejected"] != 0:
        raise ValueError(
            f"perturb drill: {pert['telemetry_rejected']} telemetry "
            "records rejected"
        )
    if pert["straggler_ratio"] < 2.0:
        raise ValueError(
            f"perturb drill: straggler inflation {pert['straggler_ratio']:.2f}"
            "x not visible in reports (expected ~3x)"
        )


def metrics() -> dict:
    return {
        "flap": flap_section(),
        "blackout": blackout_section(),
        "tenant_crash": tenant_crash_section(),
        "perturb": perturb_section(),
    }


def run() -> dict:
    return metrics()


def smoke() -> dict:
    """CI variant — host numpy over n=8; the full drills run in seconds."""
    return metrics()


if __name__ == "__main__":
    run()
