"""Reduction from a profiler trace to device metrics.

The JAX profiler writes one ``.xplane.pb`` per traced window.  Each device is a
plane ``/device:TPU:<id>`` whose line ``XLA Ops`` holds one event per
operation run; the benchmark's own host spans (``bench.*``, from
``jax.profiler.TraceAnnotation``) sit on the host plane.  Everything below
works on plain ``Op`` records, so the tests check it on synthetic traces.

Time is in seconds throughout.
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
#: collective operations as XLA names them, with their async halves
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter|"
    r"collective-broadcast|ragged-all-to-all|send|recv)")


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start: float
    dur: float
    category: str = ""

    @property
    def end(self) -> float:
        return self.start + self.dur


def op_name(event_name: str) -> str:
    """A TPU trace names a device op by its HLO text
    (``%grouped_ffn.1 = f32[...] custom-call(...)``); keep the instruction's
    name."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def is_collective(op: Op) -> bool:
    return bool(COLLECTIVE.match(op.name)) or (
        "collective" in op.category.lower())


def is_kernel(op: Op, kernel: str) -> bool:
    """A Pallas kernel's events carry the ``name`` given to ``pallas_call``,
    with XLA's numeric suffix (``grouped_ffn.3``)."""
    return op.name == kernel or op.name.startswith(kernel + ".")


def leaves(ops: list[Op]) -> list[Op]:
    """Ops that contain no other op of the list: a control-flow op that
    spans its body is dropped, so nothing is counted twice."""
    ops = sorted(ops, key=lambda o: (o.start, -o.dur))
    out = []
    for i, op in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is not None and nxt.start < op.end and nxt.end <= op.end:
            continue
        out.append(op)
    return out


def intervals(ops: list[Op], t0: float, t1: float) -> list[tuple[float, float]]:
    """Merged busy intervals of ``ops`` clipped to [t0, t1]."""
    spans = sorted((max(o.start, t0), min(o.end, t1)) for o in ops
                   if o.end > t0 and o.start < t1)
    merged: list[tuple[float, float]] = []
    for s, e in spans:
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def busy_s(ops: list[Op], t0: float, t1: float) -> float:
    """Length of the union of the ops' intervals inside [t0, t1]."""
    return sum(e - s for s, e in intervals(ops, t0, t1))


def kernel_s(ops: list[Op], kernel: str) -> float:
    return sum(o.dur for o in leaves(ops) if is_kernel(o, kernel))


def collective_s(ops: list[Op], t0: float, t1: float) -> float:
    """Union of the collective ops' intervals."""
    return busy_s([o for o in leaves(ops) if is_collective(o)], t0, t1)


def local_s(ops: list[Op], t0: float, t1: float) -> float:
    """Device time outside collective ops: the union of the other ops."""
    return busy_s([o for o in leaves(ops) if not is_collective(o)], t0, t1)


def top_ops(ops: list[Op], k: int = 10) -> list[list]:
    """The ``k`` operation names with the most device time: [[name, s]]."""
    tot: dict[str, float] = {}
    for o in leaves(ops):
        tot[o.name] = tot.get(o.name, 0.0) + o.dur
    return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(ops: list[Op], host: list[Op], t0: float, t1: float,
              k: int = 10) -> list[list]:
    """The ``k`` longest gaps in which the device ran nothing, each named by
    the innermost host span (``bench.*``) that covers its middle."""
    busy = intervals(ops, t0, t1)
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    out = []
    for s, e in gaps:
        mid = 0.5 * (s + e)
        cover = [h for h in host if h.start <= mid <= h.end]
        name = min(cover, key=lambda h: h.dur).name if cover else "none"
        out.append([name, e - s])
    return sorted(out, key=lambda g: -g[1])[:k]


@dataclasses.dataclass
class Trace:
    devices: dict[int, list[Op]]   # device id -> its ops
    host: list[Op]                 # the benchmark's host spans

    def window(self) -> tuple[float, float]:
        """The traced window: the host span ``bench.window``."""
        spans = [h for h in self.host if h.name == HOST_PREFIX + "window"]
        if not spans:
            raise ValueError("trace holds no bench.window span")
        return spans[0].start, spans[0].end


def _category(event) -> str:
    for name, value in event.stats:
        if name == "hlo_category":
            return str(value)
    return ""


def read(logdir: str | Path) -> Trace:
    """Load the newest ``.xplane.pb`` under ``logdir``."""
    import jax

    files = sorted(Path(logdir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = jax.profiler.ProfileData.from_file(str(files[-1]))
    devices: dict[int, list[Op]] = {}
    host: list[Op] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices.setdefault(int(m.group(1)), []).extend(
                    Op(op_name(ev.name), ev.start_ns * 1e-9,
                       ev.duration_ns * 1e-9, _category(ev))
                    for ev in line.events)
            elif not m:
                host.extend(Op(ev.name, ev.start_ns * 1e-9,
                               ev.duration_ns * 1e-9)
                            for ev in line.events
                            if ev.name.startswith(HOST_PREFIX))
    return Trace(devices, host)

