"""Named host spans of the fleet engine's tick, on the profiler's clock.

A :class:`Span` is a ``with`` block that, while a JAX profile is being
taken, opens a ``jax.profiler.TraceAnnotation`` of its name, so that the
profile places what the host was doing beside the device's operations.
A span of one of the engine's phases also adds its wall seconds to that
phase's entry of ``phase_seconds``, profile or not.

Without a profile a span costs a timer read and a check of the profiler
(about a microsecond), and JAX is never imported here: a process that
has not imported JAX cannot be taking a JAX profile.
"""

from __future__ import annotations

import sys
from time import perf_counter


class Span:
    """``with Span("fleet.select", phase_seconds, "select"):`` times the
    block into ``phase_seconds["select"]`` and marks it in a running
    profile; :meth:`set_metadata` attaches counts known only inside the
    block to its profile event."""

    __slots__ = ("name", "phases", "phase", "t0", "annotation")

    def __init__(self, name: str, phases: dict | None = None,
                 phase: str | None = None) -> None:
        self.name = name
        self.phases = phases
        self.phase = phase
        self.annotation = None

    def __enter__(self) -> "Span":
        profiler = sys.modules.get("jax.profiler")
        if profiler is not None and profiler.TraceAnnotation.is_enabled():
            self.annotation = profiler.TraceAnnotation(self.name)
            self.annotation.__enter__()
        self.t0 = perf_counter()
        return self

    def set_metadata(self, **counts) -> None:
        if self.annotation is not None:
            self.annotation.set_metadata(**counts)

    def __exit__(self, *exc) -> bool:
        if self.phase is not None:
            self.phases[self.phase] += perf_counter() - self.t0
        if self.annotation is not None:
            self.annotation.__exit__(*exc)
        return False
