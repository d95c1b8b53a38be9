"""One run of one benchmark cell.

``bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
looks the cell up in ``BENCHMARK.json``; the cell names a configuration
(``bench/configs/<config>.json``, whose ``runner`` is ``bench/runners/<runner>.py``
and whose plain reference is ``bench/configs/<config>.py``), a traffic mix
(``bench/traffic/<traffic>.json``, read by ``bench/generate.py``) and the
limits its answers are held to (``bench/limits/<cell>.json``).  Each per-layer
metric is read by ``bench/metrics/<metric>.py``.  Nothing here names a cell.

A run: find the chips (a TPU and as many chips as the cell asks for, or exit
non-zero with no result), set up (weights and inputs from the seed, every
shape warmed), measure for ``--seconds`` with the profiler off (``--trace 0``:
the end-to-end metrics) or on (``--trace 1``: the per-layer metrics), read
peak memory, free the program's state, compare a seed-drawn sample of the
window's answers with the plain reference, and print the result as the last
line of standard output.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import math
import statistics
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
#: longest stretch of the window the traced run records
TRACE_SECONDS = 5.0


def seed_key(seed: int):
    """A JAX key from a seed of any size (the low 32 bits, then the rest
    folded in)."""
    import jax

    return jax.random.fold_in(jax.random.key(seed % 2**32), seed >> 32)


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file of the benchmark by its path (names may hold '-')."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with the files it names."""
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list    # entries of BENCHMARK.json that this cell reports
    per_layer: list

    def reference(self):
        return load_module(BENCH / "configs" / f"{self.config['name']}.py")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, benchmark: dict | None = None) -> Cell:
    bm = benchmark or load_json(ROOT / "BENCHMARK.json")
    entries = [w for w in bm["workloads"] if w["name"] == name]
    if not entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = entries[0]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=load_json(BENCH / "configs" / f"{w['config']}.json"),
        traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(BENCH / "limits" / f"{name}.json"),
        end_to_end=[m for m in bm["end_to_end"] if applies(m, name)],
        per_layer=[m for m in bm["per_layer"] if applies(m, name)],
    )


def load_peaks(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table[device_kind]


def find_chips(n: int):
    """The first ``n`` TPU devices, or :class:`NoChip`."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX finds no devices: {e}") from e
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds {devs[0].platform}, not a TPU")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devs)}")
    return devs[:n]


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (``.jax_cache`` in the
    checkout, or ``JAX_COMPILATION_CACHE_DIR``), holding every program
    however quickly it compiled, so a second run compiles nothing."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable

    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts backend compiles and persistent-cache loads as they happen."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",)
    HITS = ("/jax/compilation_cache/cache_hits",)

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event in self.EVENTS:
            self.n += 1

    def _event(self, event, **kw):
        if event in self.HITS:
            self.n += 1


class Reservoir:
    """A uniform sample of ``k`` of the window's answers, drawn from the
    seed as they come (reservoir sampling): every call is equally likely to
    be compared, and only ``k`` answers are held at a time."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = np.random.default_rng([seed, 0x5A])
        self.items: list = []

    def offer(self, i: int, out) -> None:
        if len(self.items) < self.k:
            self.items.append((i, out))
            return
        j = int(self.rng.integers(0, i + 1))
        if j < self.k:
            self.items[j] = (i, out)


@dataclasses.dataclass
class Window:
    latencies: list     # seconds per call, each ended by block_until_ready
    seconds: float      # first call issued to last call finished
    compiles: int

    @property
    def calls(self) -> int:
        return len(self.latencies)


def run_window(runner, seconds: float, sample: Reservoir,
               counter: CompileCounter) -> Window:
    """Call the program back to back (a closed loop) until ``seconds`` have
    passed, each call waited for; the last call that starts inside finishes."""
    import jax
    from jax.profiler import TraceAnnotation

    lat = []
    before = counter.n
    with TraceAnnotation("bench.window"):
        t_start = time.perf_counter()
        i = 0
        while True:
            t0 = time.perf_counter()
            with TraceAnnotation("bench.dispatch"):
                out = runner.step(i)
            with TraceAnnotation("bench.wait"):
                jax.block_until_ready(out)
            t1 = time.perf_counter()
            lat.append(t1 - t0)
            with TraceAnnotation("bench.sample"):
                sample.offer(i, out)
            del out
            i += 1
            if t1 - t_start >= seconds:
                break
    return Window(lat, t1 - t_start, counter.n - before)


def p95(values) -> float:
    """95th percentile, as ``statistics.quantiles(n=100)`` gives it."""
    if len(values) < 2:
        return max(values)
    return statistics.quantiles(values, n=100, method="inclusive")[94]


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader sees of a traced run."""
    cell: Cell
    ops: list           # trace.Op of the device that sets the pace
    t0: float           # traced window on the trace's clock
    t1: float
    calls: int          # calls completed in the traced window
    work: dict          # the runner's counts from shapes and routing
    peaks: dict

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def idle_share(self):
        """Per cent of the traced window in which the device ran nothing."""
        from bench import trace as tr

        if not self.ops:
            return None
        return 100 * (1 - tr.busy_s(self.ops, self.t0, self.t1)
                      / self.window_s)

    def per_call_ms(self, seconds: float):
        return None if self.calls == 0 else 1e3 * seconds / self.calls

    def roofline(self, kernel: str, flops: float, nbytes: float):
        """Per cent of a kernel's device time that its least time takes:
        the larger of ``flops`` over the bf16 peak and ``nbytes`` over the
        HBM peak, both per call."""
        from bench import trace as tr

        spent = tr.kernel_s(self.ops, kernel)
        if spent == 0 or self.calls == 0:
            return None
        least = max(flops / self.peaks["bf16_flops_per_s"],
                    nbytes / self.peaks["hbm_bytes_per_s"])
        return 100 * least * self.calls / spent

    def mfu(self, flops_per_token: float):
        """Per cent of the chips' bf16 peak that the required operations of
        the tokens completed in the traced window take."""
        if self.calls == 0 or not self.ops:
            return None
        tokens_per_s = self.calls * self.work["tokens_per_call"] / self.window_s
        return 100 * flops_per_token * tokens_per_s / (
            self.work["chips"] * self.peaks["bf16_flops_per_s"])


def read_per_layer(cell: Cell, reading: Reading) -> dict:
    """Each per-layer metric of the cell from its reader; a reader that finds
    nothing to read returns None and its metric is left out."""
    out = {}
    for m in cell.per_layer:
        mod = load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = mod.read(reading)
        if value is None:
            log(f"metric {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def traced(runner, seconds: float, sample: Reservoir, counter: CompileCounter,
           cell: Cell, peaks: dict, devices):
    """The window under the profiler, reduced to per-layer metrics."""
    import jax
    from bench import trace as tr

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as logdir:
        jax.profiler.start_trace(logdir, profiler_options=opts)
        try:
            win = run_window(runner, min(seconds, TRACE_SECONDS), sample,
                             counter)
        finally:
            jax.profiler.stop_trace()
        t_read = time.perf_counter()
        trace = tr.read(logdir)
    t0, t1 = trace.window()
    used = [d.id for d in devices]
    busy = [tr.busy_s(trace.devices.get(i, []), t0, t1) for i in used]
    for i, b in zip(used, busy):
        log(f"device {i}: busy_s {b} of window_s {t1 - t0}, collective_s "
            f"{tr.collective_s(trace.devices.get(i, []), t0, t1)}")
    hot_ops = trace.devices.get(runner.hot_device_id, [])
    reading = Reading(cell, hot_ops, t0, t1, win.calls, runner.work(), peaks)
    metrics = read_per_layer(cell, reading)
    breakdown = {"device_ops": tr.top_ops(hot_ops),
                 "idle_gaps": tr.idle_gaps(hot_ops, trace.host, t0, t1)}
    log(f"trace read in {time.perf_counter() - t_read} s")
    device = {"busy_s": float(np.mean(busy)), "window_s": t1 - t0}
    return win, metrics, breakdown, device


def end_to_end(cell: Cell, values: dict) -> dict:
    out = {}
    for m in cell.end_to_end:
        if m["name"] in values:
            out[m["name"]] = {"value": float(values[m["name"]]),
                              "unit": m["unit"]}
    return out


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        devices=None, peaks: dict | None = None, after_setup=None) -> dict:
    """One run of ``cell``; returns the result line's object.

    ``after_setup(runner)`` may replace what the window calls: the controls
    of ``bench/calibrate.py`` put the reference there."""
    devices = devices if devices is not None else find_chips(cell.chips)
    peaks = peaks or load_peaks(devices[0].device_kind)
    enable_compile_cache()
    log(f"chips ready {time.perf_counter() - t_start} s after the start")
    counter = CompileCounter()
    runner = load_module(
        BENCH / "runners" / f"{cell.config['runner']}.py").Runner(
            cell, seed, devices)
    runner.setup()
    if after_setup is not None:
        after_setup(runner)
    setup_s = time.perf_counter() - t_start
    log(f"setup_s {setup_s} (compiles and cache loads in set-up: "
        f"{counter.n})")

    sample = Reservoir(int(cell.traffic["sampled_answers"]), seed)
    metrics, breakdown, device_extra = {}, None, {}
    if trace:
        win, metrics, breakdown, device_extra = traced(
            runner, seconds, sample, counter, cell, peaks, devices)
    else:
        win = run_window(runner, seconds, sample, counter)
        metrics = end_to_end(cell, dict(runner.end_to_end(win),
                                        setup_s=setup_s))
    log(f"window: {win.calls} calls in {win.seconds} s; compiles in the "
        f"window: {win.compiles}")
    peak = peak_bytes(devices)

    runner.free()
    t_check = time.perf_counter()
    checks = runner.check(sorted(sample.items, key=lambda x: x[0]))
    sample.items.clear()
    log(f"check_s {time.perf_counter() - t_check}")
    failed = runner.failed
    correct = all(math.isfinite(v) and v <= lim for v, lim in checks.values())
    result = {
        "correct": bool(correct and failed == 0),
        "attempted": win.calls,
        "failed": failed,
        "metrics": metrics,
        "device": dict({"platform": devices[0].platform,
                        "kind": devices[0].device_kind,
                        "count": len(devices),
                        "memory_peak_bytes": peak}, **device_extra),
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k} {v!r} limit {lim!r}")
    return result


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    try:
        cell = find_cell(args.workload)
    except (KeyError, FileNotFoundError) as e:
        log(f"error: {e}")
        return 2
    try:
        devices = find_chips(cell.chips)
    except NoChip as e:
        log(f"error: {e}; no result")
        return 3
    result = run(cell, args.seed, args.seconds, bool(args.trace), t_start,
                 devices)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
