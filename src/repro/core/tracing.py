"""Names the profiler shows for the PRF path: host spans and device scopes.

``host_span(name)`` marks host work as ``prf.<name>`` on the profiler's
host clock, the clock of the device's ``XLA Modules`` / ``XLA Ops``
lines. Spans nest on their thread; with no profiler running a span
costs one check. JAX dispatches asynchronously, so a host span times
what the host did, not what the device ran.

``scope(name)`` names the device ops traced under it: ``prf.<name>``
becomes a segment of each HLO instruction's ``op_name`` metadata, which
changes nothing the compiler computes. It has to be entered inside the
function being traced: a scope around the call of a jitted function
does not reach that function's ops.

Both work as ``with`` blocks and as decorators.
"""
from __future__ import annotations

import contextlib

import jax

PREFIX = "prf."


@contextlib.contextmanager
def host_span(name: str):
    with jax.profiler.TraceAnnotation(PREFIX + name):
        yield


def scope(name: str):
    return jax.named_scope(PREFIX + name)
