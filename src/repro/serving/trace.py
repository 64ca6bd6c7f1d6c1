"""Host spans and a build counter inside the serving program.

``span(name)`` marks one stretch of host work in the serving loop (the
``pd.*`` names; ``PERF.md`` lists where each one sits). Tracing is off
by default: ``span`` then returns one shared no-op context after a
single bool check, and builds, pushes and allocates nothing. After
``enable()`` each span opens a ``jax.profiler.TraceAnnotation``, so the
spans land on the profiler's host plane on the same clock as the
device operations, and pushes its name on a span stack.

The build counter listens for JAX's backend-compile event, which fires
for every program JAX builds for the backend: compiled, or loaded from
the persistent compilation cache. ``build_count()`` is the total since
import, counted always; while tracing is on each build is also charged
to the innermost open span (``"none"`` when none is open), read by
``builds()``.
"""
from __future__ import annotations

import contextlib
from typing import Dict, List

import jax

BUILD_EVENT = "/jax/core/compile/backend_compile_duration"

_on = False
_stack: List[str] = []
_builds: Dict[str, int] = {}
_total = 0
_NOOP = contextlib.nullcontext()


class _Span:
    __slots__ = ("name", "ann")

    def __init__(self, name: str):
        self.name = name
        self.ann = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self.ann.__enter__()
        _stack.append(self.name)
        return self

    def __exit__(self, *exc):
        _stack.pop()
        return self.ann.__exit__(*exc)


def span(name: str):
    """A context that marks host work as ``name`` while tracing is on."""
    if not _on:
        return _NOOP
    return _Span(name)


def enable(on: bool = True) -> None:
    """Turn the spans and the per-span build attribution on (or off)."""
    global _on
    _on = bool(on)


def builds() -> Dict[str, int]:
    """Programs built while tracing was on, by the innermost open span."""
    return dict(_builds)


def build_count() -> int:
    """Programs built in this process since this module was imported."""
    return _total


def _on_duration(event: str, duration: float, **kw) -> None:
    global _total
    if event != BUILD_EVENT:
        return
    _total += 1
    if _on:
        where = _stack[-1] if _stack else "none"
        _builds[where] = _builds.get(where, 0) + 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
