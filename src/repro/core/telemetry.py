"""Spans and counters of the program, on the clock the device trace uses.

One process-wide log, always on:

* :func:`span` times a host-side step. It records ``(name, t0, t1)`` on
  ``time.perf_counter`` and enters ``jax.profiler.TraceAnnotation`` as
  ``lk.<name>``, so while a profiler trace is being taken the step also
  sits on the trace's host plane, beside the device ops it launched.
* :func:`count` bumps a counter: an exact process total, plus one
  timestamped record in the same log, so a reader can count the bumps
  inside a time window.
* :func:`host_read` is the program's one way to read a device value to
  the host: ``np.asarray(x)`` inside the span ``sync.<why>``, counted as
  ``sync.<why>``.
* JAX's own compile events feed the counters ``jax.traces``,
  ``jax.lowerings``, ``jax.executables`` (built or loaded from the
  persistent cache) and ``jax.cache_hits``, and the timed counter
  ``jax.compile_s`` (the seconds of the first three), so the program
  itself says which step compiled again.

Readers: :func:`durations` and :func:`total` over a window, :func:`snapshot`
of the totals for operators. The log is a bounded ring guarded by one
lock: the prediction service calls the posterior from several threads.

Spans and counters belong on eager paths only. Code that a jit trace can
also reach enters them on its eager branch alone (see
``core/solvers/guarded.py``): a record made while tracing would be made
once per trace, not once per run.
"""
from __future__ import annotations

import functools
import threading
import time
from collections import deque
from typing import Any, Callable

import jax
import jax.monitoring
import jax.profiler
import numpy as np

__all__ = ["span", "count", "host_read", "durations", "total", "snapshot"]

PREFIX = "lk."
RING = 1 << 16

# (name, t0, t1, by): a span has by None, a counter bump has t0 == t1
_log: deque[tuple[str, float, float, Any]] = deque(maxlen=RING)
_totals: dict[str, float] = {}
_lock = threading.Lock()


class _Span:
    __slots__ = ("name", "_ann", "_t0")

    def __init__(self, name: str) -> None:
        self.name = name

    def __enter__(self) -> _Span:
        self._ann = jax.profiler.TraceAnnotation(PREFIX + self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        with _lock:
            _log.append((self.name, self._t0, t1, None))

    def __call__(self, fn: Callable) -> Callable:
        @functools.wraps(fn)
        def spanned(*args: Any, **kwargs: Any) -> Any:
            with _Span(self.name):
                return fn(*args, **kwargs)
        return spanned


def span(name: str) -> _Span:
    """Context manager timing one host-side step as ``name``; as a
    decorator, each call of the function is that step."""
    return _Span(name)


def count(name: str, by: float = 1) -> None:
    """Add ``by`` to the counter ``name``."""
    t = time.perf_counter()
    with _lock:
        _totals[name] = _totals.get(name, 0) + by
        _log.append((name, t, t, by))


def host_read(x: Any, why: str) -> np.ndarray:
    """``np.asarray(x)``, timed and counted as ``sync.<why>``."""
    name = "sync." + why
    with span(name):
        out = np.asarray(x)
    count(name)
    return out


def _matches(name: str, pattern: str) -> bool:
    if pattern.endswith("*"):
        return name.startswith(pattern[:-1])
    return name == pattern


def _records(pattern: str, since: float, until: float) -> list[tuple]:
    with _lock:
        log = list(_log)
    return [r for r in log
            if since <= r[1] and r[2] <= until and _matches(r[0], pattern)]


def durations(name: str, since: float = -np.inf,
              until: float = np.inf) -> list[float]:
    """Seconds of each span ``name`` that ran inside [since, until]; a
    trailing ``*`` in ``name`` matches every name with that prefix."""
    return [t1 - t0 for _, t0, t1, by in _records(name, since, until)
            if by is None]


def total(name: str, since: float | None = None,
          until: float = np.inf) -> float:
    """The counter ``name``: its exact process total when ``since`` is None,
    else the sum of its bumps inside [since, until] (a trailing ``*``
    matches a prefix there)."""
    if since is None:
        with _lock:
            return _totals.get(name, 0)
    return sum(by for _, _, _, by in _records(name, since, until)
               if by is not None)


def snapshot() -> dict[str, float]:
    """Every counter's process total."""
    with _lock:
        return dict(_totals)


# -- JAX's compile events --------------------------------------------------
_COMPILE_STEPS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.traces",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lowerings",
    "/jax/core/compile/backend_compile_duration": "jax.executables",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def _on_duration(event: str, duration: float, **_: Any) -> None:
    name = _COMPILE_STEPS.get(event)
    if name is not None:
        count(name)
        count("jax.compile_s", duration)


def _on_event(event: str, **_: Any) -> None:
    if event == _CACHE_HIT:
        count("jax.cache_hits")


for _name in (*_COMPILE_STEPS.values(), "jax.cache_hits", "jax.compile_s"):
    _totals[_name] = 0
jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
