"""Host spans and wait counters for the store's own work.

Spans name the host work of a tick (``vilamb.tick``, ``vilamb.tick.dispatch``,
``vilamb.patrol.probe``, ...) as ``jax.profiler.TraceAnnotation`` events, so a
profile shows them on the same host clock as the runtime's program launches
(``DoEnqueueProgram``), and a program run can be put down to the span that
launched it.  They are off by default: :func:`enable` switches them for the
whole process, and while off :func:`span` hands out one shared no-op context.

Wait counters are always on.  :class:`waited` times a call that blocks on the
device or on the resolver thread with ``perf_counter`` and adds
``wait.<site>.n``, ``wait.<site>.s`` and ``wait.<site>.max_ms`` to a plain
counter dict (``ProtectedStore.counters``); with spans on it is also the span
``vilamb.wait.<site>``.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict

from jax.profiler import TraceAnnotation

_on = False
_OFF = contextlib.nullcontext()


def enable(on: bool) -> None:
    """Switch the spans on or off for the whole process."""
    global _on
    _on = bool(on)


def span(name: str):
    """``vilamb.<name>`` as a profiler annotation, or a no-op when off."""
    return TraceAnnotation(f"vilamb.{name}") if _on else _OFF


def add(counters: Dict[str, float], key: str, value: float = 1) -> None:
    counters[key] = counters.get(key, 0) + value


class waited:
    """Context manager timing one blocking call at ``site``."""

    __slots__ = ("counters", "site", "t0", "ann")

    def __init__(self, counters: Dict[str, float], site: str):
        self.counters = counters
        self.site = site
        self.ann = None

    def __enter__(self):
        if _on:
            self.ann = TraceAnnotation(f"vilamb.wait.{self.site}")
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        if self.ann is not None:
            self.ann.__exit__(*exc)
        c, k = self.counters, f"wait.{self.site}"
        c[k + ".n"] = c.get(k + ".n", 0) + 1
        c[k + ".s"] = c.get(k + ".s", 0.0) + dt
        c[k + ".max_ms"] = max(c.get(k + ".max_ms", 0.0), dt * 1e3)
        return False
