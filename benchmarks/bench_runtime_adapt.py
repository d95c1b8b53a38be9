"""Execution-time adaptation: static plan vs runtime vs oracle (DESIGN.md §3).

Three policies replay the same traces through the fabric simulator:

  * **static**  — one-shot plan solved on the first window, never replanned
    (what PR 1's planner could do: a single demand matrix per call);
  * **adaptive** — the orchestration runtime's full monitor -> estimate ->
    replan -> swap loop, default policy/estimator;
  * **oracle**  — clairvoyant per-window re-solve (all windows batched
    through one ``plan_flows_batch`` dispatch), the adaptation upper bound.

Scenarios mirror the runtime acceptance criteria:

  * drifting-skew trace — adaptive must recover most of the oracle's win
    over static (paper regime: unanticipated traffic drift);
  * balanced trace — adaptive must match static within noise with zero
    replans after warmup (the "no overhead when symmetric" claim);
  * link-down event — adaptive converges to a replacement plan with all
    demand served off the dead link.

Metrics land in ``BENCH_runtime_adapt.json`` (tagged
``nimble.bench_runtime_adapt/v1``) for the per-PR bench trajectory.  All
three policies run through :class:`repro.api.Session` (DESIGN.md §5):
static vs adaptive is a one-field ``SessionSpec`` diff, and the oracle is
the session's ``run_oracle`` bookend.
"""

from __future__ import annotations

import time

import numpy as np

from repro.api import Session, SessionSpec
from repro.core.topology import Topology
from repro.runtime import (
    EventLog,
    balanced_trace,
    drifting_skew_trace,
    link_down,
)

from .common import emit

N = 8
GROUP = 4


def _session(topo, **kw) -> Session:
    return Session(SessionSpec(topology=topo, adaptivity="adaptive", **kw))


def drift_section(windows: int = 48, dwell: int = 12) -> dict:
    topo = Topology(N, group_size=GROUP)
    trace = drifting_skew_trace(N, windows, dwell=dwell)

    with Session(SessionSpec(topology=topo)) as static_sess:
        static = static_sess.run_trace(trace)
    with _session(topo) as sess:
        oracle = sess.run_oracle(trace)
        t0 = time.perf_counter()
        adaptive = sess.run_trace(trace)
        us_adaptive = (time.perf_counter() - t0) * 1e6

    speedup = static.total_completion_s / adaptive.total_completion_s
    oracle_speedup = static.total_completion_s / oracle.total_completion_s
    emit(
        f"runtime/drift/W{windows}", us_adaptive,
        f"static={static.total_completion_s * 1e3:.1f}ms "
        f"adaptive={adaptive.total_completion_s * 1e3:.1f}ms "
        f"oracle={oracle.total_completion_s * 1e3:.1f}ms "
        f"speedup={speedup:.2f}x (target >=1.3x, oracle {oracle_speedup:.2f}x) "
        f"replans={len(adaptive.replan_windows)}/{windows} "
        f"(target <=25%)",
    )
    return {
        "windows": windows,
        "static_completion_s": static.total_completion_s,
        "adaptive_completion_s": adaptive.total_completion_s,
        "oracle_completion_s": oracle.total_completion_s,
        "adaptive_speedup": speedup,
        "oracle_speedup": oracle_speedup,
        "replan_fraction": adaptive.replan_fraction,
        "replans": len(adaptive.replan_windows),
        "solves": adaptive.stats.solves,
        "cache_hits": adaptive.stats.cache_hits,
        "loop_wall_us_per_window": us_adaptive / max(windows, 1),
        # estimator/telemetry health rides along in every WindowReport
        # (ISSUE 8): full-visibility drift should end at confidence 1.0
        # with zero rejected telemetry records
        "confidence_end": float(adaptive.reports[-1].confidence),
        "telemetry_rejected": int(adaptive.reports[-1].telemetry_rejected),
    }


def balanced_section(windows: int = 30) -> dict:
    topo = Topology(N, group_size=GROUP)
    trace = balanced_trace(N, windows)
    with Session(SessionSpec(topology=topo)) as static_sess:
        static = static_sess.run_trace(trace)
    with _session(topo) as sess:
        adaptive = sess.run_trace(trace)
    ratio = adaptive.total_completion_s / static.total_completion_s
    emit(
        f"runtime/balanced/W{windows}", 0.0,
        f"adaptive/static={ratio:.4f} (target within 2%) "
        f"replans={len(adaptive.replan_windows)} (target 0 after warmup)",
    )
    return {
        "windows": windows,
        "balanced_ratio": ratio,
        "balanced_replans": len(adaptive.replan_windows),
        "confidence_end": float(adaptive.reports[-1].confidence),
        "telemetry_rejected": int(adaptive.reports[-1].telemetry_rejected),
    }


def linkdown_section(windows: int = 24, fail_at: int = 8) -> dict:
    topo = Topology(N, group_size=GROUP)
    trace = balanced_trace(N, windows)
    events = EventLog([link_down(fail_at, 0, GROUP)])
    with _session(topo) as sess:
        res = sess.run_trace(trace, events=events)
    pre = np.median([r.completion_s for r in res.reports[:fail_at]])
    # convergence: first window after the fault whose completion is within
    # 2x the pre-fault median (the degraded fabric has less capacity, so
    # exact parity is not expected)
    converged = next(
        (
            r.window
            for r in res.reports[fail_at:]
            if r.completion_s <= 2.0 * pre
        ),
        None,
    )
    tail = res.reports[-1].completion_s
    emit(
        f"runtime/linkdown/W{windows}", 0.0,
        f"fault@w{fail_at} converged@w{converged} "
        f"tail={tail * 1e3:.2f}ms (pre-fault {pre * 1e3:.2f}ms)",
    )
    return {
        "windows": windows,
        "fail_window": fail_at,
        "converged_window": converged,
        "recovery_windows": (
            converged - fail_at if converged is not None else None
        ),
        "tail_completion_s": float(tail),
        "prefault_completion_s": float(pre),
    }


def metrics(windows: int = 48, dwell: int = 12) -> dict:
    out = {}
    out.update({"drift": drift_section(windows, dwell)})
    out.update({"balanced": balanced_section()})
    out.update({"linkdown": linkdown_section()})
    return out


def run() -> dict:
    return metrics()


def smoke() -> dict:
    """CI variant — the discrete-event loop is host numpy over n=8, so the
    full acceptance-size traces already run in a few seconds."""
    return metrics()


if __name__ == "__main__":
    run()
