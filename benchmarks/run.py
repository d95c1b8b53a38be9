"""Benchmark runner — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus section markers) and
writes the aggregate to benchmarks/results.csv.

  Fig 6(a,c)  bench_p2p_intra       intra-node multi-path bandwidth
  Fig 6(b,d)  bench_p2p_inter       inter-node multi-rail bandwidth
  Fig 7       bench_alltoallv_skew  skewed All-to-Allv sweep
  Fig 8       bench_moe_e2e         MoE end-to-end breakdown
  Table I     bench_algo_overhead   planner overhead vs comm time
  §V-E        bench_multitenant     background-tenant interference
  §III/V      bench_runtime_adapt   execution-time adaptation vs static/oracle
  (arbiter)   bench_fairness        multi-tenant arbitration + Jain fairness
  (faults)    bench_faults          fault drills: flap/blackout/crash recovery
  (serve)     bench_serve           serving control plane: scenario SLO drills
  (lint)      bench_lint            static invariant checker verdict

``--smoke`` runs the planner-overhead, runtime-adaptation, fairness,
fault-drill, and serving-control-plane sections in a few seconds and
writes ``BENCH_algo_overhead.json`` / ``BENCH_runtime_adapt.json`` /
``BENCH_fairness.json`` / ``BENCH_faults.json`` / ``BENCH_serve.json`` at
the repo root, so planner-latency, adaptation, arbitration, robustness,
and serving-SLO regressions show up in the bench trajectory on every PR.
Four gates close the run: ``mutual_drift`` validates the fairness JSON's
mutual-drift section (schema + the >= 1.0x combined-drain threshold the
calibrated price-recency defaults must hold, ISSUE 5), ``fault_drills``
validates the fault JSON against the recovery/availability thresholds of
ISSUE 6 (flap recovery <= 2 windows with bounded replans, blackout drain
>= the static baseline, post-eviction survivor within 2% of
never-joined), ``serve_slo`` validates the serving scenarios of ISSUE 7
(every scenario holds its declared SLOs; steady parity >= 0.99x;
elephant_victim and flap_under_load beat static on combined drain; churn
leaves the survivor's steady state within 2% of a never-churned run),
``obs_overhead`` validates the flight-recorder contract of ISSUE 8 (a
traced drift run byte-identical to the untraced one, with a valid
``nimble.trace/v1`` export — writes
``BENCH_obs.json``), ``static_gate`` runs the ``repro.analysis``
invariant checker over ``src/repro`` (ISSUE 9: zero live findings with
the shipped empty baseline, plus ``schemas.lock.json`` freshness —
writes ``BENCH_lint.json``), and ``session_api`` pushes one arbitrated
two-tenant window through the ``repro.api.Session`` facade with the
exported JSON validated against the ``nimble.fabric_fairness/v1`` schema
(the full facade selfcheck — including the serving check 6, the tracing
check 7, and the static-analysis check 8 — is
``python -m repro.api.selfcheck``).

``--compare`` re-runs the smoke benches and diffs every numeric metric
against the committed ``BENCH_*.json`` baselines, printing a per-metric
delta table and exiting nonzero when any non-wall-clock metric moved more
than ``--threshold`` (default 10%) — the pre-merge "did my change move
the benches" check.

Every ``--smoke`` run also appends one timestamped ``trajectory/`` row to
``benchmarks/results.csv`` — gate verdicts plus the headline metric from
each ``BENCH_*.json`` — so the repo-level trajectory accumulates across
PRs instead of living only in the per-run JSONs (full ``main()`` runs
rewrite the bench rows but preserve the accumulated trajectory rows).
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(ROOT, "src")
if _SRC not in sys.path:   # benches usually run with PYTHONPATH=src already
    sys.path.insert(0, _SRC)


def _write_metrics(fname: str, metrics: dict, kind: str | None = None) -> str:
    from repro.jsonio import tag, write_json_file

    if kind is not None:
        metrics = tag(kind, metrics)
    out = os.path.join(ROOT, fname)
    write_json_file(out, metrics)
    return out


RESULTS_CSV = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "results.csv")
CSV_HEADER = "name,us_per_call,derived\n"

#: trajectory-row schema: v2 added the leading ``schema=`` token itself
#: plus the ``obs_overhead`` gate and headline (ISSUE 8); v1 rows (no
#: token) predate it and --compare treats them as unversioned
TRAJECTORY_SCHEMA = 2


def _append_trajectory_row(gates: dict, headline: dict) -> str:
    """Append one timestamped ``trajectory/`` row to benchmarks/results.csv.

    The row carries the bench schema version, the gate verdicts, and one
    headline metric per ``BENCH_*.json`` so the repo accumulates a
    cross-PR trend line that survives full ``main()`` rewrites.  The
    derived field is space-separated ``k=v`` pairs — no commas, it lives
    in a CSV cell.
    """
    import datetime

    stamp = datetime.datetime.now(datetime.timezone.utc).strftime(
        "%Y-%m-%dT%H:%M:%SZ"
    )
    verdicts = "+".join(
        f"{name}:{'pass' if ok else 'FAIL'}" for name, ok in gates.items()
    )
    parts = [f"schema=v{TRAJECTORY_SCHEMA}", f"gates={verdicts}"]
    parts += [f"{k}={v}" for k, v in headline.items()]
    derived = " ".join(parts)
    if "," in derived:
        raise ValueError(f"trajectory derived field grew a comma: {derived!r}")
    fresh = not os.path.exists(RESULTS_CSV)
    with open(RESULTS_CSV, "a") as f:
        if fresh:
            f.write(CSV_HEADER)
        f.write(f"trajectory/{stamp},0.000,{derived}\n")
    return stamp


def smoke() -> None:
    from . import (
        bench_algo_overhead,
        bench_fairness,
        bench_faults,
        bench_lint,
        bench_obs,
        bench_runtime_adapt,
        bench_serve,
        common,
    )

    gates: dict = {}
    gate_errors: list = []

    def _gate(name: str, fn) -> None:
        try:
            fn()
            gates[name] = True
        except Exception as exc:  # record, log trajectory, re-raise below
            gates[name] = False
            gate_errors.append((name, exc))

    print("name,us_per_call,derived")
    print("# --- table1_overhead (smoke) ---")
    algo_metrics = bench_algo_overhead.smoke()
    out = _write_metrics("BENCH_algo_overhead.json", algo_metrics)
    print("# --- runtime_adapt (smoke) ---")
    adapt_metrics = bench_runtime_adapt.smoke()
    out2 = _write_metrics(
        "BENCH_runtime_adapt.json",
        adapt_metrics,
        kind="bench_runtime_adapt",
    )
    print("# --- fairness (smoke) ---")
    fairness_metrics = bench_fairness.smoke()
    out3 = _write_metrics(
        "BENCH_fairness.json",
        fairness_metrics,
        kind="bench_fairness",
    )
    print("# --- mutual_drift gate (smoke) ---")
    # schema + threshold gate (ISSUE 5): the calibrated recency defaults
    # must keep the mutual-drift scenario at >= 1.0x combined drain vs the
    # unpriced baseline; raises on regression
    _gate(
        "mutual_drift",
        lambda: bench_fairness.validate_mutual_drift(
            fairness_metrics["mutual_drift"]
        ),
    )
    md = fairness_metrics["mutual_drift"]
    print(
        f"# mutual_drift: win={md['win']:.4f}x (legacy "
        f"{md['win_legacy']:.4f}x) >= 1.0x "
        f"{'OK' if gates['mutual_drift'] else 'FAIL'}"
    )
    print("# --- faults (smoke) ---")
    fault_metrics = bench_faults.smoke()
    out4 = _write_metrics(
        "BENCH_faults.json",
        fault_metrics,
        kind="bench_faults",
    )
    print("# --- fault_drills gate (smoke) ---")
    # recovery/availability thresholds (ISSUE 6); raises on regression
    _gate("fault_drills", lambda: bench_faults.validate_faults(fault_metrics))
    print(
        f"# fault_drills: flap recovery "
        f"{fault_metrics['flap']['recovery_windows']}w, blackout "
        f"{fault_metrics['blackout']['adaptive_static_ratio']:.3f}x static, "
        f"survivor {fault_metrics['tenant_crash']['survivor_solo_ratio']:.4f}"
        f"x solo {'OK' if gates['fault_drills'] else 'FAIL'}"
    )
    print("# --- serve (smoke) ---")
    serve_metrics = bench_serve.smoke()
    out5 = _write_metrics("BENCH_serve.json", serve_metrics, kind="serve")
    print("# --- serve_slo gate (smoke) ---")
    # scenario SLOs + adaptive-vs-static thresholds (ISSUE 7); raises on
    # any scenario missing its declared gates
    _gate("serve_slo", lambda: bench_serve.validate_serve(serve_metrics))
    print(
        f"# serve_slo: steady {serve_metrics['steady']['win']:.4f}x, "
        f"elephant {serve_metrics['elephant_victim']['win']:.4f}x, flap "
        f"{serve_metrics['flap_under_load']['win']:.4f}x static; churn tail "
        f"{serve_metrics['churn']['tail_ratio']:.4f}x control "
        f"{'OK' if gates['serve_slo'] else 'FAIL'}"
    )
    print("# --- obs (smoke) ---")
    obs_metrics = bench_obs.smoke()
    out6 = _write_metrics("BENCH_obs.json", obs_metrics, kind="bench_obs")
    print("# --- obs_overhead gate (smoke) ---")
    # flight-recorder contract: recorded run byte-identical to
    # plain, with a valid, non-empty trace
    _gate("obs_overhead", lambda: bench_obs.validate_obs(obs_metrics))
    print(
        f"# obs_overhead: identical={obs_metrics['identical']}, "
        f"trace_events={obs_metrics['trace_events']} "
        f"{'OK' if gates['obs_overhead'] else 'FAIL'}"
    )
    print("# --- lint (smoke) ---")
    lint_metrics = bench_lint.smoke()
    out7 = _write_metrics("BENCH_lint.json", lint_metrics, kind="bench_lint")
    print("# --- static_gate (smoke) ---")
    # static invariant checker (ISSUE 9/10): zero live findings over
    # src/repro with the shipped empty baseline, fresh schemas.lock.json
    # + retrace.lock.json, and a non-empty trace-boundary inventory with
    # zero PLAN_DEPENDENT sites
    _gate("static_gate", lambda: bench_lint.validate_lint(lint_metrics))
    print(
        f"# static_gate: {lint_metrics['files']} files, "
        f"{lint_metrics['findings']} finding(s), "
        f"{lint_metrics['suppressed']} suppressed, "
        f"lock_fresh={lint_metrics['lock_fresh']}, "
        f"retrace_sites={lint_metrics['retrace_sites']}, "
        f"plan_dependent={lint_metrics['retrace_plan_dependent']} "
        f"{'OK' if gates['static_gate'] else 'FAIL'}"
    )
    print("# --- session_api (smoke) ---")
    from repro.api.selfcheck import smoke_session_check

    check: dict = {}

    def _session_gate() -> None:
        check.update(smoke_session_check())  # raises on schema violation

    _gate("session_api", _session_gate)
    print(f"# session_api: {check.get('summary', 'FAILED')}")

    headline = {
        "host_speedup": f"{algo_metrics['host_speedup']:.2f}x",
        "drift_speedup": f"{adapt_metrics['drift']['adaptive_speedup']:.3f}x",
        "mutual_drift_win": f"{md['win']:.4f}x",
        "four_tenant_jain": f"{fairness_metrics['four_tenant']['jain_index']:.4f}",
        "flap_recovery": f"{fault_metrics['flap']['recovery_windows']}w",
        "crash_survivor": (
            f"{fault_metrics['tenant_crash']['survivor_solo_ratio']:.4f}x"
        ),
        "serve_steady": f"{serve_metrics['steady']['win']:.4f}x",
        "serve_elephant": f"{serve_metrics['elephant_victim']['win']:.4f}x",
        "serve_flap": f"{serve_metrics['flap_under_load']['win']:.4f}x",
        "serve_churn_tail": f"{serve_metrics['churn']['tail_ratio']:.4f}x",
        "obs_swapped": f"{obs_metrics['plans_swapped']}",
        "lint": (
            f"{'clean' if lint_metrics['clean'] else 'DIRTY'}"
            f"({lint_metrics['files']}f/"
            f"{lint_metrics['retrace_sites']}s)"
        ),
    }
    stamp = _append_trajectory_row(gates, headline)
    print(f"# trajectory: appended {stamp} row to {RESULTS_CSV}")
    print(
        f"# wrote {len(common.ROWS)} rows; metrics -> {out}, {out2}, "
        f"{out3}, {out4}, {out5}, {out6}, {out7}"
    )
    if gate_errors:
        name, exc = gate_errors[0]
        raise RuntimeError(f"smoke gate {name!r} failed: {exc}") from exc


def main() -> None:
    from . import (
        bench_algo_overhead,
        bench_alltoallv_skew,
        bench_fairness,
        bench_faults,
        bench_lint,
        bench_moe_e2e,
        bench_multitenant,
        bench_obs,
        bench_p2p_async,
        bench_p2p_inter,
        bench_p2p_intra,
        bench_runtime_adapt,
        bench_serve,
        common,
    )

    sections = [
        ("fig6_intra", bench_p2p_intra),
        ("fig6_inter", bench_p2p_inter),
        ("async_p2p", bench_p2p_async),
        ("fig7_alltoallv", bench_alltoallv_skew),
        ("fig8_moe", bench_moe_e2e),
        ("table1_overhead", bench_algo_overhead),
        ("vE_multitenant", bench_multitenant),
        ("runtime_adapt", bench_runtime_adapt),
        ("fairness", bench_fairness),
        ("faults", bench_faults),
        ("serve", bench_serve),
        ("obs", bench_obs),
        ("lint", bench_lint),
    ]
    metric_files = {
        "runtime_adapt": ("BENCH_runtime_adapt.json", "bench_runtime_adapt"),
        "fairness": ("BENCH_fairness.json", "bench_fairness"),
        "faults": ("BENCH_faults.json", "bench_faults"),
        "serve": ("BENCH_serve.json", "serve"),
        "obs": ("BENCH_obs.json", "bench_obs"),
        "lint": ("BENCH_lint.json", "bench_lint"),
    }
    print("name,us_per_call,derived")
    for name, mod in sections:
        print(f"# --- {name} ---")
        metrics = mod.run()
        if name in metric_files and metrics:
            fname, kind = metric_files[name]
            _write_metrics(fname, metrics, kind=kind)
    # rewrite the bench rows but carry over the accumulated cross-PR
    # trajectory rows --smoke appends
    trajectory: list = []
    if os.path.exists(RESULTS_CSV):
        with open(RESULTS_CSV) as f:
            trajectory = [
                line for line in f if line.startswith("trajectory/")
            ]
    with open(RESULTS_CSV, "w") as f:
        f.write(CSV_HEADER)
        for row in common.ROWS:
            f.write(f"{row[0]},{row[1]:.3f},{row[2]}\n")
        f.writelines(trajectory)
    print(
        f"# wrote {len(common.ROWS)} rows to {RESULTS_CSV} "
        f"(+{len(trajectory)} trajectory rows preserved)"
    )


#: the committed per-PR bench baselines --compare diffs against
BENCH_FILES = (
    "BENCH_algo_overhead.json",
    "BENCH_runtime_adapt.json",
    "BENCH_fairness.json",
    "BENCH_faults.json",
    "BENCH_serve.json",
    "BENCH_obs.json",
    "BENCH_lint.json",
)

#: metric-path fragments whose values are wall-clock (machine-dependent)
#: — reported in the delta table but never gated
VOLATILE_FRAGMENTS = ("wall", "_us", "us_per", "overhead", "elapsed",
                      "host_speedup", "jit_trace_ms")

#: default relative-delta gate for --compare
COMPARE_THRESHOLD = 0.10


def _numeric_leaves(obj, prefix: str = ""):
    """Yield ``(dotted.path, float)`` for every numeric leaf (bools are
    config, not metrics; the schema envelope is identity, not data)."""
    if isinstance(obj, dict):
        for k in sorted(obj):
            if k == "schema":
                continue
            yield from _numeric_leaves(obj[k], f"{prefix}{k}." if prefix
                                       else f"{k}.")
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            yield from _numeric_leaves(v, f"{prefix}{i}.")
    elif isinstance(obj, (int, float)) and not isinstance(obj, bool):
        yield prefix.rstrip("."), float(obj)


def _is_volatile(path: str) -> bool:
    return any(frag in path for frag in VOLATILE_FRAGMENTS)


def compare(threshold: float = COMPARE_THRESHOLD) -> int:
    """Re-run the smoke benches and diff against the committed baselines.

    Loads the repo-root ``BENCH_*.json`` snapshots *before* the rerun
    overwrites them, then prints a per-metric delta table (relative
    change against the committed value).  Non-volatile metrics whose
    relative delta exceeds ``threshold`` are regressions: each is named,
    and the exit status is nonzero if any exist.  Wall-clock metrics
    (``*_us``, ``*wall*``, ``overhead``, ``host_speedup``,
    ``jit_trace_ms`` — anything derived from machine timing) are shown
    for context but never gated — they measure the machine, not the code.
    """
    import json

    baselines: dict = {}
    for fname in BENCH_FILES:
        path = os.path.join(ROOT, fname)
        if os.path.exists(path):
            with open(path) as f:
                baselines[fname] = dict(_numeric_leaves(json.load(f)))
    if not baselines:
        print("# --compare: no committed BENCH_*.json baselines found")
        return 2

    smoke()  # rewrites the BENCH files with this machine's numbers

    regressions: list = []
    print(f"\n# --- compare vs committed baselines "
          f"(threshold {threshold:.0%}) ---")
    print("file,metric,committed,current,delta,gated")
    for fname, base in sorted(baselines.items()):
        with open(os.path.join(ROOT, fname)) as f:
            fresh = dict(_numeric_leaves(json.load(f)))
        for path in sorted(set(base) & set(fresh)):
            old, new = base[path], fresh[path]
            if old == new:
                continue
            rel = abs(new - old) / max(abs(old), 1e-12)
            gated = not _is_volatile(path)
            flag = "gated" if gated else "volatile"
            if gated and rel > threshold:
                regressions.append((fname, path, old, new, rel))
                flag = "REGRESSION"
            print(f"{fname},{path},{old:.6g},{new:.6g},{rel:+.2%},{flag}")
        for path in sorted(set(base) - set(fresh)):
            regressions.append((fname, path, base[path], None, float("inf")))
            print(f"{fname},{path},{base[path]:.6g},MISSING,,REGRESSION")
    if regressions:
        print(f"# compare: {len(regressions)} metric(s) moved more than "
              f"{threshold:.0%} vs the committed baselines:")
        for fname, path, old, new, rel in regressions:
            print(f"#   {fname}:{path}  {old:.6g} -> "
                  f"{'MISSING' if new is None else f'{new:.6g}'}")
        return 1
    print("# compare: all gated metrics within threshold")
    return 0


def _parse_threshold(argv) -> float:
    for i, arg in enumerate(argv):
        if arg == "--threshold" and i + 1 < len(argv):
            return float(argv[i + 1])
        if arg.startswith("--threshold="):
            return float(arg.split("=", 1)[1])
    return COMPARE_THRESHOLD


if __name__ == "__main__":
    if "--compare" in sys.argv[1:]:
        sys.exit(compare(_parse_threshold(sys.argv[1:])))
    elif "--smoke" in sys.argv[1:]:
        smoke()
    else:
        main()
