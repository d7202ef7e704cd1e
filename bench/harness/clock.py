"""Host clock, compile counting and the benchmark's own spans.

``Compiles`` is the compile-seconds part of the program's
``chip_smoke.PhaseClock``, copied: it listens to JAX's
``backend_compile_duration`` event, so set-up can report what it
compiled and the window can show that it compiled nothing.

``span`` records a host span on the benchmark's clock and, while a
trace is being taken, writes the same span into the profiler's trace
(``bench.<name>``), so that idle gaps on the device can be named after
what the benchmark was doing.
"""
from __future__ import annotations

import contextlib
import time

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

now = time.perf_counter


class Compiles:
    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.count += 1


@contextlib.contextmanager
def span(name: str, record: list | None = None):
    import jax

    t0 = now()
    with jax.profiler.TraceAnnotation(f"bench.{name}"):
        yield
    if record is not None:
        record.append((name, t0, now()))
