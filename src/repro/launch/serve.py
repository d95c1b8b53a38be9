"""Serving launcher: scenario control plane + batched generation.

Scenario mode — run a registry (or JSON-file) scenario through the
continuous-traffic control plane (DESIGN.md §10) and print the SLO
verdict:

    PYTHONPATH=src python -m repro.launch.serve --scenario steady
    PYTHONPATH=src python -m repro.launch.serve --scenario path/to/spec.json \
        --mode static --json report.json
    PYTHONPATH=src python -m repro.launch.serve --list-scenarios

With ``--trace-out PATH`` the run is flight-recorded (DESIGN.md §11): a
:class:`repro.obs.FlightRecorder` rides the adaptive arm and the
resulting ``nimble.trace/v1`` record — valid Chrome/Perfetto trace JSON
with one correlation id across serve / runtime / fabric / planner — is
written to PATH (open it at ``ui.perfetto.dev`` or ``chrome://tracing``).
``--metrics-out PATH`` writes the final ``nimble.metrics/v1`` snapshot;
either flag also prints trace and plan-provenance summaries:

    PYTHONPATH=src python -m repro.launch.serve --scenario flap_under_load \
        --mode adaptive --trace-out trace.json --metrics-out metrics.json

Generation mode — batched greedy/temperature token generation through
``ServeEngine``:

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --reduced \
        --batch 4 --prompt-len 8 --new-tokens 16
"""

from __future__ import annotations

import argparse
import time


def _run_scenario(args) -> int:
    from repro.jsonio import write_json_file
    from repro.serve import (
        evaluate_scenario,
        load_scenario,
        run_scenario,
        scenario_names,
    )

    spec = load_scenario(args.scenario)
    recorder = None
    if args.trace_out or args.metrics_out:
        from repro.obs import FlightRecorder

        recorder = FlightRecorder()
    t0 = time.time()
    if args.mode == "both":
        res = evaluate_scenario(spec, recorder=recorder)
        report, slo = res["adaptive"], res["slo"]
    else:
        report, slo = run_scenario(spec, args.mode, recorder=recorder), None
    dt = time.time() - t0

    tenants = report.tenants
    print(
        f"[serve] scenario {spec.name!r}: {spec.windows} windows, "
        f"{len(tenants)} tenant(s), mode={report.mode} ({dt:.1f}s)"
    )
    print(
        f"[serve] cluster: total {report.total_completion_s:.4f}s, "
        f"median {report.median_latency_s() * 1e3:.2f}ms, "
        f"availability {report.availability:.2f}, "
        f"Jain {report.jain_index:.3f}"
    )
    for name, led in sorted(tenants.items()):
        life = f"w{led.joined}-" + (
            f"w{led.left}" if led.left is not None else "end"
        )
        print(
            f"[serve]   {name}: {life} {led.windows}w "
            f"{led.completion_s:.4f}s drain, {led.replans} replans"
            + (" (crashed)" if led.crashed else "")
        )
    if slo is not None:
        for gate, v in slo["gates"].items():
            val = v["value"]
            shown = f"{val:.3f}" if isinstance(val, float) else str(val)
            print(
                f"[serve]   gate {gate}: "
                f"{'PASS' if v['ok'] else 'FAIL'} "
                f"(value {shown}, limit {v['limit']})"
            )
        print(f"[serve] SLO: {'PASS' if slo['pass'] else 'FAIL'}")
    if recorder is not None:
        from repro.obs import validate_trace

        trace = recorder.export_trace()
        info = validate_trace(trace)
        print(
            f"[serve] trace: {info['events']} events, {info['spans']} spans, "
            f"layers={sorted(info['cats'])}, corr={info['correlation_id']}"
        )
        print(
            f"[serve] provenance: {len(recorder.provenance)} plans issued, "
            f"{len(recorder.provenance.swapped())} swapped"
        )
        if args.trace_out:
            write_json_file(args.trace_out, trace)
            print(f"[serve] trace -> {args.trace_out}")
        if args.metrics_out:
            write_json_file(args.metrics_out, recorder.metrics_snapshot())
            print(f"[serve] metrics -> {args.metrics_out}")
    if args.json:
        obj = report.to_json_obj()
        if slo is not None:
            obj["slo"] = slo
        write_json_file(args.json, obj)
        print(f"[serve] report -> {args.json}")
    return 0 if slo is None or slo["pass"] else 1


def _run_generate(args):
    import jax
    import numpy as np

    from repro.configs.base import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.registry import build_model
    from repro.serve.engine import ServeEngine
    from repro.sharding.context import SINGLE

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg, SINGLE)
    params = model.init(jax.random.PRNGKey(args.seed))
    engine = ServeEngine(model, params,
                         max_len=args.prompt_len + args.new_tokens)

    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)).astype(
        np.int32
    )
    t0 = time.time()
    out = engine.generate(prompts, n_new=args.new_tokens,
                          temperature=args.temperature, seed=args.seed)
    dt = time.time() - t0
    tok_s = args.batch * args.new_tokens / dt
    print(f"[serve] {cfg.name}: generated {out.shape} in {dt:.2f}s "
          f"({tok_s:.1f} tok/s)")
    print("[serve] sample:", out[0][:12].tolist())
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    # scenario mode
    ap.add_argument("--scenario", default=None,
                    help="registry name or scenario JSON path")
    ap.add_argument("--mode", default="both",
                    choices=("adaptive", "static", "both"),
                    help="control-plane arm; 'both' also gates the SLOs")
    ap.add_argument("--json", default=None,
                    help="write the nimble.serve/v1 report here")
    ap.add_argument("--trace-out", metavar="PATH", default=None,
                    help="flight-record the run and write the "
                         "nimble.trace/v1 Chrome trace JSON here")
    ap.add_argument("--metrics-out", metavar="PATH", default=None,
                    help="write the final nimble.metrics/v1 snapshot here")
    ap.add_argument("--list-scenarios", action="store_true")
    # generation mode
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.list_scenarios:
        from repro.serve import scenario_names
        print("\n".join(scenario_names()))
        return 0
    if args.scenario is not None:
        return _run_scenario(args)
    return _run_generate(args)


if __name__ == "__main__":
    main()
