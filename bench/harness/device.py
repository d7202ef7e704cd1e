"""The chips a run holds, and their published peaks (``bench/peaks.json``)."""
from __future__ import annotations

import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def require(chips: int):
    """The first ``chips`` TPU devices; raises ``NoChip`` otherwise."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: jax.devices()[0].platform = {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds {len(devices)}")
    return devices[:chips]


def describe(devices) -> dict:
    d = devices[0]
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}
    print(f"device: {info}", file=sys.stderr, flush=True)
    return info


def peaks(kind: str) -> dict:
    """Published peaks of one chip of ``kind``; a kind not in the table is an error."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} has no entry in bench/peaks.json")
    return table[kind]


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
