"""Host spans and counters of the mesh trainer's round, on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``; its
keyword identifiers (``round``, ``worker``) become the event's stats in the
profiler's trace, so a reduction of a device trace can put each stretch of
device time or idle time down to what the host was doing then.  The
annotations land in a trace only while a profiler runs; with none running a
span costs about a microsecond.  The same span also adds its host seconds
(``time.perf_counter``) to the phase table it is given: the round's seconds
per span name, which ``MeshTrainer`` hands out as ``StepRecord.phases``.

Compiles are counted by one process-wide listener on JAX's compile event
(persistent-cache loads included): JAX's listeners are process-wide and
cannot be taken off, so one listener serves every trainer, and a trainer
reads the counts as differences from what they were when it was built.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Optional

import jax

PREFIX = "repro."
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@contextlib.contextmanager
def span(name: str, phases: Optional[dict] = None, **ids):
    """``repro.<name>`` around the body; its seconds are added to
    ``phases[name]`` (where ``phases`` is given) when the body returns."""
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation(PREFIX + name, **ids):
        yield
    if phases is not None:
        phases[name] = phases.get(name, 0.0) + (time.perf_counter() - t)


def span_until_ready(name: str, out, since: float, **ids) -> float:
    """On a thread of its own: ``repro.<name>`` from now until ``out`` is
    ready on its devices; returns the seconds from ``since`` (a
    ``perf_counter`` reading of the caller's) to then."""
    with jax.profiler.TraceAnnotation(PREFIX + name, **ids):
        jax.block_until_ready(out)
    return time.perf_counter() - since


class _CompileCount:
    def __init__(self):
        self._lock = threading.Lock()
        self.count = 0
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.count += 1
                self.seconds += duration

    def read(self) -> tuple[int, float]:
        with self._lock:
            return self.count, self.seconds


_compiles: Optional[_CompileCount] = None
_compiles_lock = threading.Lock()


def compiles() -> tuple[int, float]:
    """Backend compiles in this process since the listener was registered
    (on the first call) and their seconds."""
    global _compiles
    with _compiles_lock:
        if _compiles is None:
            _compiles = _CompileCount()
            jax.monitoring.register_event_duration_secs_listener(_compiles)
    return _compiles.read()
