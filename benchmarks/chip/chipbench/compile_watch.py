"""Compilation seen through JAX's own monitoring events (the chip smoke's
``CompileClock``, counting events as well as seconds).

A backend compile (``backend_compile_duration``) is what a warm set-up must
leave none of for the window; tracing and lowering events are counted
beside it, since a program that traces again on every call shows there
first."""
from __future__ import annotations

import collections

import jax

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileWatch:
    """Seconds and events of tracing, lowering and compiling, by event."""

    def __init__(self):
        self.seconds = 0.0
        self.by_event = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            self.by_event[event] += 1
