"""Regenerate the EXPERIMENTS.md roofline tables from experiments/dryrun/*.json.

    PYTHONPATH=src python experiments/make_report.py > experiments/roofline_tables.md

Also appends the execution-time orchestration section when the repo root
holds a ``BENCH_runtime_adapt.json`` (tagged ``nimble.bench_runtime_adapt``
via the shared ``repro.jsonio`` schema), the fabric-arbiter fairness
section from ``BENCH_fairness.json`` (``nimble.bench_fairness``), the
fault-drill section from ``BENCH_faults.json`` (``nimble.bench_faults``),
the serving-control-plane SLO table from ``BENCH_serve.json``
(``nimble.serve``, DESIGN.md §10), and the static-analysis verdict line
from ``BENCH_lint.json`` (``nimble.bench_lint``, DESIGN.md §12).
"""

import glob
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load(pattern):
    out = {}
    for f in sorted(glob.glob(os.path.join(HERE, "dryrun", pattern))):
        r = json.load(open(f))
        out[(r["arch"], r["shape"])] = r
    return out


def fmt_row(r):
    ro = r.get("roofline")
    if not ro:
        return None
    mx = max(ro["compute_s"], ro["memory_s"], ro["collective_s"])
    frac = ro["compute_s"] / mx if mx else 0.0
    return (
        f"| {r['arch']} | {r['shape']} | {ro['compute_s']:.3e} "
        f"| {ro['memory_s']:.3e} | {ro['collective_s']:.3e} "
        f"| {ro['dominant']} | {ro['useful_flops_ratio']:.3f} | {frac:.4f} |"
    )


def table(recs, title):
    print(f"\n### {title}\n")
    print("| arch | shape | compute (s) | memory (s) | collective (s) "
          "| dominant | 6ND/HLO | roofline frac |")
    print("|---|---|---|---|---|---|---|---|")
    skips = []
    for (a, s), r in sorted(recs.items()):
        row = fmt_row(r)
        if row is None:
            skips.append((a, s, r["status"]))
            continue
        print(row)
    for a, s, st in skips:
        print(f"| {a} | {s} | — | — | — | {st} | — | — |")


def multipod_status(recs):
    print("\n### Multi-pod (2x16x16 = 512 chips) compile status\n")
    ok = sum(1 for r in recs.values() if r["status"] == "ok")
    sk = [(a, s) for (a, s), r in recs.items() if r["status"] != "ok"]
    print(f"{ok}/{len(recs)} lower+compile OK; skips: "
          + ", ".join(f"{a}x{s}" for a, s in sk))
    print("\n| arch | shape | peak bytes/device | collective (s) | dominant |")
    print("|---|---|---|---|---|")
    for (a, s), r in sorted(recs.items()):
        ro = r.get("roofline")
        if not ro:
            continue
        pk = r["bytes_per_device"]["peak"]
        print(f"| {a} | {s} | {pk:.2e} | {ro['collective_s']:.3e} "
              f"| {ro['dominant']} |")


def runtime_adapt_section():
    """Orchestration-runtime adaptation table from BENCH_runtime_adapt.json."""
    rec = _load_tagged("BENCH_runtime_adapt.json", "bench_runtime_adapt")
    if rec is None:
        return
    print("\n### Execution-time orchestration (drift / balance / fault)\n")
    d, b, l = rec["drift"], rec["balanced"], rec["linkdown"]
    print("| scenario | windows | result |")
    print("|---|---|---|")
    print(
        f"| drifting skew | {d['windows']} | adaptive {d['adaptive_speedup']:.2f}x "
        f"vs static (oracle {d['oracle_speedup']:.2f}x), "
        f"{d['replans']} replans ({d['replan_fraction']:.0%}), "
        f"{d['cache_hits']} cache hits"
        + (
            f", confidence {d['confidence_end']:.2f}, "
            f"{d['telemetry_rejected']} rejected"
            if "confidence_end" in d
            else ""
        )
        + " |"
    )
    print(
        f"| balanced | {b['windows']} | adaptive/static = "
        f"{b['balanced_ratio']:.4f}, {b['balanced_replans']} replans |"
    )
    print(
        f"| link down | {l['windows']} | fault@w{l['fail_window']}, "
        f"replacement plan in {l['recovery_windows']} window(s) |"
    )


def _load_tagged(fname, expect_kind):
    path = os.path.join(ROOT, fname)
    if not os.path.exists(path):
        return None
    try:
        from repro.jsonio import read_json_file, schema_kind
        rec = read_json_file(path)
        kind = schema_kind(rec)
    except ImportError:  # no PYTHONPATH=src; same on-disk format
        rec = json.load(open(path))
        kind = rec.get("schema", "").split(".", 1)[-1].rsplit("/", 1)[0]
    return rec if kind == expect_kind else None


def fairness_section():
    """Fabric-arbiter fairness table from BENCH_fairness.json."""
    rec = _load_tagged("BENCH_fairness.json", "bench_fairness")
    if rec is None:
        return
    print("\n### Fabric arbiter (multi-tenant congestion pricing)\n")
    h, r, f = rec["host_coplan"], rec["runtime_adaptive"], rec["four_tenant"]
    print("| scenario | combined drain (independent -> arbitrated) "
          "| win | Jain |")
    print("|---|---|---|---|")
    for name, s in (
        ("skew vs elephant (host)", h),
        (f"arbitrated runtime ({r['windows']}w)", r),
        ("four tenants", f),
    ):
        print(
            f"| {name} | {s['independent_combined_drain_s'] * 1e3:.2f}ms -> "
            f"{s['arbitrated_combined_drain_s'] * 1e3:.2f}ms "
            f"| {s['win']:.2f}x | {s['jain_index']:.3f} |"
        )
    pts = rec["weights_sweep"]["points"]
    print(
        "\nweight sweep (skew tenant): "
        + ", ".join(
            f"w={p['weight']:g}: own {p['skew_drain_s'] * 1e3:.2f}ms / "
            f"combined {p['combined_drain_s'] * 1e3:.2f}ms"
            for p in pts
        )
    )
    md = rec.get("mutual_drift")
    if md is not None:
        arms = md["arms"]
        print(
            f"\nmutual drift ({md['windows']}w, dwell {md['dwell']}): "
            f"unpriced {arms['unpriced']['combined_drain_s'] * 1e3:.1f}ms, "
            f"raw-ledger prices {md['win_legacy']:.3f}x, "
            f"calibrated recency {md['win']:.3f}x "
            f"({arms['calibrated']['reprices']} swap-boundary reprices, "
            f"{arms['calibrated']['price_hints']} hints; gate: >= 1.0x)"
        )
    # gated vs no-trigger windows (WindowReport.trigger_reason): "gated"
    # means a real trigger fired and the fabric gate suppressed it — not
    # the same as a window where nothing triggered at all
    gated = r.get("gated_windows")
    if gated is not None:
        triggers = r.get("gated_triggers") or {}
        detail = (
            " (" + ", ".join(
                f"{k} x{v}" for k, v in sorted(triggers.items())
            ) + ")"
            if triggers
            else ""
        )
        print(
            f"\narbitrated runtime: {len(gated)} gated window(s) "
            f"{detail or '(none)'} out of {r['windows']} — triggers "
            "suppressed by the admission gate, distinct from "
            "trigger-free windows"
        )


def faults_section():
    """Fault-drill table from BENCH_faults.json (DESIGN.md §9)."""
    rec = _load_tagged("BENCH_faults.json", "bench_faults")
    if rec is None:
        return
    print("\n### Fault drills (graceful degradation)\n")
    print("| drill | windows | result |")
    print("|---|---|---|")
    fl = rec["flap"]
    print(
        f"| link flap | {fl['windows']} | {fl['flap_events']} events, "
        f"{fl['topology_replans_backoff']} topology replans with backoff "
        f"(vs {fl['topology_replans_storm']} without, "
        f"{fl['suppressed_windows']} suppressed), recovered "
        f"{fl['recovery_windows']} window(s) after the final restore, "
        f"availability {fl['availability']:.2f} |"
    )
    bl = rec["blackout"]
    print(
        f"| telemetry blackout | {bl['windows']} | "
        f"{bl['blackout_windows']}-window blackout across a drift phase: "
        f"adaptive stayed {bl['adaptive_static_ratio']:.2f}x static on "
        f"last-good demand, confidence back to "
        f"{bl['confidence_end']:.2f}, availability "
        f"{bl['availability']:.2f} |"
    )
    cr = rec["tenant_crash"]
    print(
        f"| tenant crash | {cr['windows']} | crash@w{cr['crash_window']}, "
        f"{cr['evictions']} staleness eviction; survivor tail "
        f"{cr['survivor_solo_ratio']:.4f}x the never-joined reference; "
        f"double teardown "
        f"{'OK' if cr['double_teardown_ok'] else 'FAILED'} |"
    )
    pt = rec["perturb"]
    print(
        f"| straggler+elephant+dropout | {pt['windows']} | straggler "
        f"inflation {pt['straggler_ratio']:.2f}x visible, "
        f"{pt['telemetry_rejected']} telemetry records rejected |"
    )


def serve_section():
    """Serving control-plane SLO table from BENCH_serve.json (§10)."""
    rec = _load_tagged("BENCH_serve.json", "serve")
    if rec is None:
        return
    print("\n### Serving control plane (scenario SLO drills)\n")
    print("| scenario | windows | tenants | SLO | adaptive vs static "
          "| Jain | availability |")
    print("|---|---|---|---|---|---|---|")
    for name in ("steady", "elephant_victim", "flap_under_load"):
        s = rec.get(name)
        if s is None:
            continue
        rec_w = s.get("recovery_windows")
        extra = f", recovery {rec_w}w" if rec_w is not None else ""
        print(
            f"| {name} | {s['windows']} | {s['tenants']} "
            f"| {'PASS' if s['slo_pass'] else 'FAIL'} | {s['win']:.3f}x "
            f"| {s['jain']:.3f} | {s['availability']:.3f}{extra} |"
        )
    ch = rec.get("churn")
    if ch is not None:
        print(
            f"\nchurn storm ({ch['windows']}w, {ch['churned_tenants']} "
            f"scavengers, last leave w{ch['last_leave_window']}): survivor "
            f"steady-state {ch['tail_ratio']:.4f}x the never-churned "
            f"control (gate |r-1| <= 0.02), whole run "
            f"{ch['total_ratio']:.4f}x (gate <= 1.02)"
        )
    gates = rec.get("steady", {}).get("gates")
    if gates:
        print(
            "\nsteady gate values: "
            + ", ".join(
                f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}"
                for k, v in sorted(gates.items())
            )
        )


def obs_section():
    """Flight-recorder contract table from BENCH_obs.json (§11)."""
    rec = _load_tagged("BENCH_obs.json", "bench_obs")
    if rec is None:
        return
    print("\n### Observability (flight recorder)\n")
    print(
        f"traced drift run ({rec['windows']}w): "
        f"recorded arm byte-identical: {rec['identical']}; trace "
        f"{rec['trace_events']} events / {rec['trace_spans']} spans across "
        f"{', '.join(rec['layers'])}; provenance {rec['plans_issued']} "
        f"plans issued, {rec['plans_swapped']} swapped"
    )


def lint_section():
    """One-line static-analysis verdict from BENCH_lint.json (§12)."""
    rec = _load_tagged("BENCH_lint.json", "bench_lint")
    if rec is None:
        return
    print("\n### Static analysis (invariant checker)\n")
    line = (
        f"{'clean' if rec['clean'] else 'DIRTY'}: {rec['files']} files, "
        f"{rec['rules']} rules, {rec['findings']} live finding(s) "
        f"({rec['suppressed']} suppressed, {rec['baselined']} baselined), "
        f"schema lock {'fresh' if rec['lock_fresh'] else 'STALE'}"
    )
    if "retrace_sites" in rec:  # ISSUE 10 fields, absent in older records
        line += (
            f"; retrace inventory {rec['retrace_sites']} sites "
            f"({rec['retrace_plan_dependent']} plan-dependent, "
            f"{rec['retrace_window_dependent']} window-dependent), "
            f"retrace lock "
            f"{'fresh' if rec['retrace_lock_fresh'] else 'STALE'}"
        )
    print(line)


def main():
    base = load("*_16x16_nimble.json")
    opt = load("*_16x16_nimble_alt0.25_opt.json")
    mp = load("*_2x16x16_nimble.json")
    table(base, "Baseline roofline — single pod (16x16), paper-faithful "
                "defaults (alt_frac 0.5, scan FFN path captured pre-§Perf)")
    if opt:
        table(opt, "Post-§Perf roofline — single pod, optimized defaults "
                   "(dense grouped FFN, segment dataplane, chunked/assoc "
                   "xLSTM, alt_frac 0.25, last_only prefill)")
        print("\n### Baseline vs optimized, dominant term\n")
        print("| arch | shape | baseline max-term (s) | optimized (s) "
              "| speedup |")
        print("|---|---|---|---|---|")
        for key in sorted(base):
            rb, ro_ = base[key], opt.get(key)
            if not ro_ or "roofline" not in rb or "roofline" not in ro_:
                continue
            b = max(rb["roofline"][k] for k in
                    ("compute_s", "memory_s", "collective_s"))
            o = max(ro_["roofline"][k] for k in
                    ("compute_s", "memory_s", "collective_s"))
            if b <= 0:
                continue
            print(f"| {key[0]} | {key[1]} | {b:.3e} | {o:.3e} "
                  f"| {b / o:.2f}x |")
    multipod_status(mp)
    runtime_adapt_section()
    fairness_section()
    faults_section()
    serve_section()
    obs_section()
    lint_section()


if __name__ == "__main__":
    main()
