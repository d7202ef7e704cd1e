"""The program's own names in a profiler trace, read beside ``trace.py``.

``trace.py`` keeps only the benchmark's host spans and names each device
op after its instruction. The program names its work itself
(``repro.core.tracing``): host spans and device scopes whose names start
with ``prf.``. This reads, from the same trace:

* each op's scope: the innermost ``prf.`` segment of its instruction's
  ``op_name`` metadata, which a v5e trace keeps as the ``tf_op`` stat of
  the event's metadata. ``jax.profiler.ProfileData`` does not show
  event metadata, so ``tf_ops`` reads it from the ``.xplane.pb`` bytes
  (an ``XSpace`` protobuf) itself. A fusion carries the ``op_name`` of
  its root, so fused work that crosses a scope boundary counts under
  the root's scope, and a fusion whose root the compiler made (a layout
  bitcast, a tuple) counts under none;
* the program's host spans beside the benchmark's. A host span times
  what the host did; JAX dispatches asynchronously;
* idle gaps cut where a host span starts or ends, each piece named
  ``<innermost span open over it> -> <next module>``. A gap at a job's
  start opens while the host still waits on the previous job, so the
  span open at the gap's start (``trace.idle_gaps``) names the wait,
  not the work that holds the device idle.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

from . import trace

PREFIXES = ("bench.", "prf.")


def scope_of(op_name: str) -> str:
    """The innermost ``prf.`` segment of an ``op_name``, or ""."""
    segs = [s.split(":")[0] for s in op_name.split("/") if s.startswith("prf.")]
    return segs[-1] if segs else ""


@dataclasses.dataclass
class Scoped:
    trace: trace.Trace
    scopes: dict                       # (device, start, name, opcode) -> scope
    spans: list                        # (name, start, end): bench. and prf.

    def scope(self, op: trace.Op) -> str:
        return self.scopes.get((op.device, op.start, op.name, op.opcode), "")

    def op_seconds(self, pred) -> float:
        """Device seconds of the ops ``pred(op, scope)`` accepts."""
        return sum(o.dur for o in self.trace.ops if pred(o, self.scope(o)))

    def span_seconds(self, name: str) -> float:
        """Host seconds of the spans called ``name`` that start in the window."""
        lo, hi = self.trace.window
        return sum(e - s for n, s, e in self.spans if n == name and lo <= s < hi)

    def gaps(self) -> list[tuple[str, float]]:
        """Idle gaps of device 0 cut at host-span bounds: (name, seconds)."""
        lo, hi = self.trace.window
        dev = min((d for d, *_ in self.trace.modules), default=0)
        mods = [(s, e, n) for d, n, s, e in self.trace.modules if d == dev]
        cuts = sorted({t for _, s, e in self.spans for t in (s, e) if lo < t < hi})
        out = []
        for name, s, e in _gap_intervals(mods, lo, hi):
            points = [s] + [t for t in cuts if s < t < e] + [e]
            for a, b in zip(points, points[1:]):
                out.append((f"{trace._host_at(self.spans, a)} -> {name}", b - a))
        return out


def _gap_intervals(mods, lo, hi):
    """(next module, start, end) of every stretch of [lo, hi) with no module."""
    out, t = [], lo
    mods = sorted(mods)
    for i in range(len(mods) + 1):
        s = mods[i][0] if i < len(mods) else hi
        nxt = mods[i][2] if i < len(mods) else "end of window"
        s = min(max(s, lo), hi)
        if s > t:
            out.append((nxt, t, s))
        if i < len(mods):
            t = max(t, min(mods[i][1], hi))
    return out


def reduce_events(device_modules, device_ops, host_spans, window=None) -> Scoped:
    """``trace.reduce_events`` with each op as (hlo_text, start_s, dur_s,
    op_name) and ``host_spans`` holding ``prf.`` spans too."""
    bench = [s for s in host_spans if s[0].startswith("bench.")]
    tr = trace.reduce_events(
        device_modules, {d: [o[:3] for o in ops] for d, ops in device_ops.items()},
        bench, window)
    # Keyed by name too: a container and the first op of its body start together.
    scopes = {(d, o[1], *trace.parse_op(o[0])[:2]): scope_of(o[3])
              for d, ops in device_ops.items() for o in ops}
    return Scoped(tr, scopes, sorted(host_spans, key=lambda s: s[1]))


def load(trace_dir: str) -> Scoped:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    with open(paths[-1], "rb") as f:
        xspace = f.read()
    return from_profile(ProfileData.from_serialized_xspace(xspace), tf_ops(xspace))


def from_profile(pd, ops_tf: dict) -> Scoped:
    """``pd``'s events, with ``ops_tf`` (``tf_ops`` of the same trace)
    giving each ``XLA Ops`` event its ``op_name``."""
    mods, ops, spans = {}, {}, []
    for plane in pd.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        for line in plane.lines:
            if m and line.name == "XLA Modules":
                mods.setdefault(int(m.group(1)), []).extend(
                    (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in line.events)
            elif m and line.name == "XLA Ops":
                names = ops_tf.get(plane.name, [])
                ops.setdefault(int(m.group(1)), []).extend(
                    (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                     names[i][1] if i < len(names) and names[i][0] == e.name else "")
                    for i, e in enumerate(line.events))
            elif plane.name.startswith("/host"):
                spans.extend(
                    (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events if e.name.startswith(PREFIXES))
    return reduce_events(mods, ops, spans)


def tf_ops(xspace: bytes) -> dict:
    """{device plane name: [(event name, tf_op) of each ``XLA Ops`` event,
    in order]} from a serialized ``XSpace`` (``tsl/profiler/protobuf/
    xplane.proto``: XSpace.planes 1; XPlane name 2, lines 3,
    event_metadata 4, stat_metadata 5; XLine name 2, events 4; XEvent
    metadata_id 1; XEventMetadata name 2, stats 5; XStat metadata_id 1,
    str_value 5, ref_value 7; XStatMetadata name 2; map entries key 1,
    value 2)."""
    out = {}
    for num, plane in _fields(xspace):
        if num != 1:
            continue
        f = _group(plane)
        name = f.get(2, [b""])[0].decode()
        if not name.startswith("/device:"):
            continue
        stat_names = {}
        for entry in f.get(5, []):
            e = _group(entry)
            stat_names[e[1][0]] = _group(e[2][0]).get(2, [b""])[0].decode()
        events = {}
        for entry in f.get(4, []):
            e = _group(entry)
            md = _group(e[2][0])
            tf_op = ""
            for stat in md.get(5, []):
                st = _group(stat)
                if stat_names.get(st[1][0]) == "tf_op":
                    tf_op = (st[5][0].decode() if 5 in st
                             else stat_names.get(st.get(7, [0])[0], ""))
            events[e[1][0]] = (md.get(2, [b""])[0].decode(), tf_op)
        for line in f.get(3, []):
            ln = _group(line)
            if ln.get(2, [b""])[0] == b"XLA Ops":
                out.setdefault(name, []).extend(
                    events.get(_group(ev).get(1, [0])[0], ("", "")) for ev in ln.get(4, []))
    return out


def _group(buf) -> dict:
    out: dict = {}
    for num, v in _fields(buf):
        out.setdefault(num, []).append(v)
    return out


def _fields(buf):
    """(field number, value) of each field of a protobuf message: an int
    for a varint, the bytes of a length-delimited field. Fixed-width
    fields (doubles) are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        else:
            i += 8 if wire == 1 else 4
            continue
        yield key >> 3, v


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7
