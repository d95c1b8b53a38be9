"""Tiny versions of the benchmark's cells, run on the CPU.

Each kind keeps its cell's runner, reference, traffic pattern and limits and
shrinks only the sizes, so that a test can drive a whole run (set-up, window,
check) in seconds.  The chip look is skipped: the run is handed the CPU
devices and a peaks row of its own.  Runs that need four devices go through
``python tests/bench/tiny.py <kind> [--fault F | --control]`` in a process
started with four virtual CPU devices; the result is its last line.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

PEAKS = {"source": "made up for the CPU tests", "bf16_flops_per_s": 1e12,
         "hbm_bytes_per_s": 1e11, "ici_bits_per_s": 8e11}

_A2AV = dict(harness.load_json(harness.BENCH / "configs" / "a2av-v5e-2x2.json"),
             chunk_bytes=1024, max_chunks=8)
_MOE = dict(harness.load_json(harness.BENCH / "configs" / "paper-moe-8e.json"),
            d_model=64, d_ff=128)
_GRANITE = dict(
    harness.load_json(harness.BENCH / "configs" / "granite-moe-1b-a400m.json"),
    hidden_size=64, intermediate_size=32, num_attention_heads=4,
    num_key_value_heads=2, num_local_experts=8, num_experts_per_tok=2,
    num_hidden_layers=2, vocab_size=128)


def _traffic(name: str, **kw) -> dict:
    return dict(harness.load_json(harness.BENCH / "traffic" / f"{name}.json"),
                **kw)


#: kind -> (cell whose limits and metrics it keeps, chips, config, traffic)
KINDS = {
    "a2av": ("a2av-hot0.9", 4, _A2AV,
             _traffic("a2av-hot0.9", per_rank_bytes=8 * 1024)),
    "moe1": ("moe8e-1chip-hot0.9", 1, _MOE,
             _traffic("route-hot0.9-t4096", tokens=256)),
    "moe4": ("moe8e-ep4-hot0.9", 4, _MOE,
             _traffic("route-hot0.9-t16384", tokens=1024)),
    "train": ("granite-1chip-train", 1, _GRANITE,
              _traffic("lm-b1-s2048", seq=128, batches=4)),
}


def cell(kind: str) -> harness.Cell:
    """The kind's cell at tiny sizes, with the cell's limits and metrics
    (those of ``BENCHMARK.json`` that name it, if it names it)."""
    name, chips, config, traffic = KINDS[kind]
    bm = harness.load_json(harness.ROOT / "BENCHMARK.json")
    return harness.Cell(
        name=name, chips=chips, config=config, traffic=traffic,
        limits=harness.load_json(harness.BENCH / "limits" / f"{name}.json"),
        end_to_end=[m for m in bm["end_to_end"] if harness.applies(m, name)],
        per_layer=[m for m in bm["per_layer"] if harness.applies(m, name)])


def run(kind: str, seed: int = 7, seconds: float = 0.2,
        trace: bool = False, control: bool = False) -> dict:
    import jax
    from bench.calibrate import CONTROLS

    c = cell(kind)
    # CPU programs in the checkout's compile cache serve no chip run
    harness.enable_compile_cache = lambda: None
    return harness.run(c, seed, seconds, trace, time.perf_counter(),
                       devices=jax.devices()[:c.chips], peaks=PEAKS,
                       after_setup=(CONTROLS[c.config["runner"]] if control
                                    else None))


def main(argv=None) -> int:
    from bench.calibrate import FAULTS, Patched

    ap = argparse.ArgumentParser()
    ap.add_argument("kind", choices=sorted(KINDS))
    ap.add_argument("--fault", choices=sorted(FAULTS), default=None)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args(argv)
    with Patched(FAULTS[args.fault] if args.fault else lambda _set: None):
        res = run(args.kind, args.seed, trace=bool(args.trace),
                  control=args.control)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
