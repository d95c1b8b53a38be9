"""Flight-recorder contract: traced vs untraced runtime loop (DESIGN.md §11).

A run without a recorder must produce exactly the JSON it produced before
``repro.obs`` existed, and a run *with* a recorder must not change the
simulation's outputs either (tracing observes, never steers).  Both are
checked by comparing the full ``run_trace`` result JSON of the two arms.

The traced arm's artifacts are validated on the way out: the exported
``nimble.trace/v1`` passes :func:`repro.obs.validate_trace` and every
swap the runtime performed has a provenance record in the audit log.

Metrics land in ``BENCH_obs.json`` (tagged ``nimble.bench_obs/v1``);
``validate_obs`` is the ``obs_overhead`` smoke gate.  The gate once also
bounded the traced loop's CPU wall clock; that ratio swung 0.76x-1.10x
from run to run on the same code, so it measured the machine, not the
recorder, and is gone.  Speed is measured on the chip.
"""

from __future__ import annotations

import json

from repro.api import Session, SessionSpec
from repro.core.topology import Topology
from repro.obs import FlightRecorder, validate_trace
from repro.runtime import drifting_skew_trace

from .common import emit

N = 8
GROUP = 4


def _run_arm(topo, trace, recorder=None) -> str:
    """The result JSON of one full drift run."""
    with Session(
        SessionSpec(topology=topo, adaptivity="adaptive"), recorder=recorder
    ) as sess:
        res = sess.run_trace(trace)
    return json.dumps(res.to_json_obj(), sort_keys=True)


def obs_section(windows: int = 48, dwell: int = 12) -> dict:
    topo = Topology(N, group_size=GROUP)
    trace = drifting_skew_trace(N, windows, dwell=dwell)

    # one traced run kept for artifact validation (its recorder outlives
    # the session — provenance is an audit trail, DESIGN.md §11)
    recorder = FlightRecorder()
    traced_json = _run_arm(topo, trace, recorder=recorder)
    plain_json = _run_arm(topo, trace)
    identical = traced_json == plain_json

    info = validate_trace(recorder.export_trace())
    swaps = len(recorder.provenance.swapped())
    unswapped = sum(
        1 for p in recorder.provenance
        if not p.swapped and not p.abandoned and p.trigger != "initial"
    )
    emit(
        f"obs/contract/W{windows}", 0.0,
        f"identical={identical} trace_events={info['events']} "
        f"plans={len(recorder.provenance)} swapped={swaps}",
    )
    return {
        "windows": windows,
        "identical": bool(identical),
        "trace_events": int(info["events"]),
        "trace_spans": int(info["spans"]),
        "layers": sorted(info["cats"]),
        "plans_issued": len(recorder.provenance),
        "plans_swapped": swaps,
        "plans_pending_or_lost": unswapped,
    }


def validate_obs(metrics: dict) -> None:
    """The ``obs_overhead`` gate: raise on any broken observability claim."""
    m = metrics["obs"] if "obs" in metrics else metrics
    if not m["identical"]:
        raise AssertionError(
            "flight-recorded run diverged from the plain run — tracing "
            "must observe, never steer"
        )
    if m["trace_events"] <= 0 or m["trace_spans"] <= 0:
        raise AssertionError("traced run exported an empty trace")
    for layer in ("runtime", "planner"):
        if layer not in m["layers"]:
            raise AssertionError(f"trace is missing the {layer!r} layer")
    if m["plans_swapped"] < 1:
        raise AssertionError("drift run swapped no plans — trace is inert")


def metrics(windows: int = 48, dwell: int = 12) -> dict:
    return obs_section(windows, dwell)


def run() -> dict:
    return metrics()


def smoke() -> dict:
    return metrics()


if __name__ == "__main__":
    run()
