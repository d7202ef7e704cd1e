"""Reduction of a JAX profiler trace to the numbers the metrics read.

A device plane (``/device:TPU:<n>``) has an ``XLA Modules`` line (one
event per program execution) and an ``XLA Ops`` line (one event per HLO
instruction run, nested: a ``while`` event spans the ops of its body).
Host planes carry the benchmark's own spans (``TraceAnnotation`` names
that start with ``bench.``) on the profiler's clock.

* busy: the union of a device's module intervals inside the window;
* ops: leaf instructions only (``while``, ``conditional`` and ``call``
  are containers and would count their bodies twice), each with its
  opcode, its instruction name without the ``.N`` suffix, and the
  module whose interval holds it;
* idle gaps: the stretches of the window with no module running, each
  named after the innermost benchmark span open at its start and the
  module that ran next.

``reduce_events`` works on plain tuples so that it can be tested on a
small recorded trace without a device.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

CONTAINERS = {"while", "conditional", "call"}
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_OP = re.compile(r"^%?([\w.\-]+) = (.*?)\s([a-z][a-z0-9\-]*)\(")


@dataclasses.dataclass
class Op:
    device: int
    name: str        # instruction name without its ".N" suffix
    opcode: str
    module: str      # module name without its "(id)" suffix
    start: float     # seconds on the profiler clock
    dur: float
    shape: str = ""  # result shape text, e.g. "f32[2,512]{1,0}"

    def dims(self) -> list[int]:
        """Dimensions of the (first) result array."""
        m = re.search(r"\[([\d,]*)\]", self.shape)
        return [int(d) for d in m.group(1).split(",") if d] if m else []

    @property
    def kernel(self) -> bool:
        return self.opcode == "custom-call"

    @property
    def collective(self) -> bool:
        return any(self.opcode.startswith(c) for c in COLLECTIVES)


@dataclasses.dataclass
class Trace:
    window: tuple[float, float]
    busy_s: list[float]          # per device, inside the window
    ops: list[Op]                # leaf ops inside the window, every device
    modules: list[tuple[int, str, float, float]]  # (device, name, start, end)
    gaps: list[tuple[str, float]]  # (attribution, seconds), every gap, device 0
    spans: list[tuple[str, float, float]]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def n_devices(self) -> int:
        return len(self.busy_s)

    def op_seconds(self, pred) -> float:
        """Device seconds of the ops ``pred`` accepts, summed over devices."""
        return sum(o.dur for o in self.ops if pred(o))


def parse_op(text: str) -> tuple[str, str, str]:
    """(name without suffix, opcode, result shape) of an HLO instruction's text."""
    m = _OP.match(text)
    if not m:
        return text.split(" ", 1)[0].lstrip("%"), "", ""
    return re.sub(r"\.\d+$", "", m.group(1)), m.group(3), m.group(2)


def _module_name(text: str) -> str:
    return re.sub(r"\(\d+\)$", "", text)


def _union(intervals, lo, hi) -> float:
    total, end = 0.0, lo
    for s, e in sorted(intervals):
        s, e = max(s, end), min(e, hi)
        if e > s:
            total += e - s
            end = e
    return total


def reduce_events(device_modules, device_ops, host_spans, window=None) -> Trace:
    """device_modules: {device: [(name, start_s, dur_s)]};
    device_ops: {device: [(hlo_text, start_s, dur_s)]};
    host_spans: [(name, start_s, end_s)] of the benchmark.
    ``window`` defaults to the ``bench.window`` span."""
    if window is None:
        w = [s for s in host_spans if s[0] == "bench.window"]
        window = (w[0][1], w[0][2]) if w else (
            min(s for m in device_modules.values() for _, s, _ in m),
            max(s + d for m in device_modules.values() for _, s, d in m),
        )
    lo, hi = window
    devices = sorted(device_modules)
    busy, modules, ops = [], [], []
    for dev in devices:
        mods = sorted((s, s + d, _module_name(n)) for n, s, d in device_modules[dev])
        busy.append(_union([(s, e) for s, e, _ in mods], lo, hi))
        modules += [(dev, n, s, e) for s, e, n in mods if e > lo and s < hi]
        starts = [s for s, _, _ in mods]
        for text, s, d in device_ops.get(dev, []):
            if s < lo or s >= hi:
                continue
            name, opcode, shape = parse_op(text)
            if opcode in CONTAINERS:
                continue
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][2] if i >= 0 and s < mods[i][1] else ""
            ops.append(Op(dev, name, opcode, mod, s, d, shape))
    gaps = idle_gaps(
        [(s, e, n) for d, n, s, e in modules if d == (devices[0] if devices else 0)],
        host_spans, lo, hi,
    )
    return Trace((lo, hi), busy, ops, modules, gaps, list(host_spans))


def idle_gaps(mods, host_spans, lo, hi):
    """Every stretch of [lo, hi) with no module running on one device,
    named ``<innermost bench span at its start> -> <next module>``."""
    out, t = [], lo
    mods = sorted(mods)
    for i in range(len(mods) + 1):
        s = mods[i][0] if i < len(mods) else hi
        nxt = mods[i][2] if i < len(mods) else "end of window"
        s = min(max(s, lo), hi)
        if s > t:
            out.append((f"{_host_at(host_spans, t)} -> {nxt}", s - t))
        if i < len(mods):
            t = max(t, min(mods[i][1], hi))
    return out


def _host_at(host_spans, t) -> str:
    best = None
    for name, s, e in host_spans:
        if s <= t < e and name != "bench.window" and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "bench.window"


def top(items, n=10):
    """[[name, seconds]] of the n largest sums by name."""
    acc: dict[str, float] = {}
    for name, sec in items:
        acc[name] = acc.get(name, 0.0) + sec
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(tr: Trace) -> dict:
    ops = [(f"{o.module}/{o.name}[{o.opcode}]", o.dur / tr.n_devices) for o in tr.ops]
    return {"device_ops": top(ops), "idle_gaps": top(tr.gaps)}


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    mods, ops, spans = {}, {}, []
    for plane in pd.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        for line in plane.lines:
            if m:
                dev = int(m.group(1))
                if line.name == "XLA Modules":
                    mods.setdefault(dev, []).extend(
                        (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in line.events)
                elif line.name == "XLA Ops":
                    ops.setdefault(dev, []).extend(
                        (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in line.events)
            elif plane.name.startswith("/host"):
                spans.extend(
                    (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events if e.name.startswith("bench."))
    return reduce_events(mods, ops, spans)
