"""Counts of what JAX traces, compiles and fetches, per thread.

One process-wide ``jax.monitoring`` listener (installed once, on import
of :mod:`repro.obs`) folds JAX's compile-path events into a running
total and into a count of the thread that raised them. JAX raises them
synchronously on the thread that calls the jitted function, so the
difference of :func:`thread_counts` around a call is exactly what that
call traced and compiled, however many other threads compile meanwhile.

* ``traces``: jaxpr traces (one per traced function, nested jits too);
  ``traced`` holds them per function name;
* ``compiles``: backend compile requests — a program fetched from the
  persistent compilation cache counts here and in ``cache_hits``;
* ``cache_hits`` / ``cache_misses``: persistent-cache outcomes (both
  stay 0 with no cache directory configured; a miss is counted when
  the compiled program is written back);
* ``trace_s``: seconds spent tracing to jaxprs and lowering them.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Counter

import jax.monitoring

__all__ = ["JitCounts", "install", "thread_counts", "totals"]

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"


@dataclasses.dataclass
class JitCounts:
    traces: int = 0
    compiles: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    trace_s: float = 0.0
    traced: Counter[str] = dataclasses.field(
        default_factory=collections.Counter)

    def copy(self) -> "JitCounts":
        return dataclasses.replace(self, traced=collections.Counter(
            self.traced))

    def __sub__(self, other: "JitCounts") -> "JitCounts":
        traced = collections.Counter(self.traced)
        traced.subtract(other.traced)
        return JitCounts(self.traces - other.traces,
                         self.compiles - other.compiles,
                         self.cache_hits - other.cache_hits,
                         self.cache_misses - other.cache_misses,
                         self.trace_s - other.trace_s, +traced)


_lock = threading.Lock()
_total = JitCounts()
_local = threading.local()
_installed = False


def _mine() -> JitCounts:
    c = getattr(_local, "counts", None)
    if c is None:
        c = _local.counts = JitCounts()
    return c


def _on_event(event: str, **_) -> None:
    if event not in (_HIT, _MISS):
        return
    with _lock:
        for c in (_mine(), _total):
            if event == _HIT:
                c.cache_hits += 1
            else:
                c.cache_misses += 1


def _on_duration(event: str, secs: float, fun_name: str = "", **_) -> None:
    if event not in (_TRACE, _LOWER, _COMPILE):
        return
    with _lock:
        for c in (_mine(), _total):
            if event == _COMPILE:
                c.compiles += 1
                continue
            c.trace_s += secs
            if event == _TRACE:
                c.traces += 1
                c.traced[fun_name] += 1


def install() -> None:
    """Register the listener (idempotent)."""
    global _installed
    with _lock:
        if _installed:
            return
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True


def thread_counts() -> JitCounts:
    """A copy of the calling thread's counts since it started."""
    with _lock:
        return _mine().copy()


def totals() -> JitCounts:
    """A copy of the whole process's counts."""
    with _lock:
        return _total.copy()
