"""Set-up, measured window and spans of one benchmark cell.

A traffic mix names its ``kind``; the kind's ``cycle(cell, k)`` lives in
``perfbench/kinds/<kind>.py`` and drives the program's own entry points.
A window runs whole cycles until ``seconds`` have passed, so every window
holds the same mix of round kinds. Each round keeps what the comparison
needs, as device arrays, and reads nothing back that the program does not
read itself.
"""
from __future__ import annotations

import contextlib
import importlib.util
import time
from dataclasses import dataclass, field
from pathlib import Path

import jax
import jax.monitoring
import numpy as np
from repro import core

from . import curves


class CompileCounter:
    """Counts executables built through jax.monitoring: ``n`` compiled or
    loaded from the persistent compilation cache, ``hits`` loaded, and
    ``seconds`` spent in the backend step that compiles or loads them."""

    def __init__(self):
        self.n = 0
        self.hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.n, self.hits, self.seconds

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


class Spans:
    """Host spans of the benchmark: (name, start_s, end_s) on the
    perf_counter clock, also written into the profiler's trace as
    ``pb.<name>`` annotations while a trace is being taken."""

    def __init__(self):
        self.records: list[tuple[str, float, float]] = []
        self.annotate = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = (jax.profiler.TraceAnnotation(f"pb.{name}") if self.annotate
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann:
            yield
        self.records.append((name, t0, time.perf_counter()))

    def durations(self, name: str, since: float = -np.inf) -> list[float]:
        return [b - a for n, a, b in self.records if n == name and a >= since]


@dataclass
class Round:
    """What one round answered, kept for the comparison after the window."""
    task: int
    Y: np.ndarray          # (n, m) what the program was given, score space
    mask: np.ndarray
    params: tuple          # the fit's answer: raw hyper-parameters
    alpha: object          # device (n, m): the posterior solve's answer
    mean: np.ndarray       # (n,) final-epoch mean, y units
    var: np.ndarray        # (n,) final-epoch predictive variance, y units
    sweeps: object         # device scalar: operator sweeps of that solve


@dataclass
class Cell:
    """One configuration under one traffic mix, with its tasks."""
    config: dict
    traffic: dict
    seed: int
    tasks: list = field(default_factory=list)
    spans: Spans = field(default_factory=Spans)
    rounds: list = field(default_factory=list)
    record: bool = True
    carry: object = None        # what the traffic kind keeps between cycles

    @property
    def gp(self) -> core.LKGPConfig:
        return core.LKGPConfig(**self.config["gp"])

    def make_tasks(self):
        c, t = self.config, self.traffic
        rng = np.random.default_rng(self.seed)
        seeds = rng.integers(0, 2**31 - 1, size=t["tasks"])
        self.tasks = [curves.sample_task(int(s), c["n"], c["m"], c["d"],
                                         observed_fraction=tuple(
                                             t.get("observed_fraction",
                                                   (0.1, 0.9))))
                      for s in seeds]

    def keep(self, task: int, state, Y, mask, mean, var):
        if not self.record:
            return
        post = core.posterior(state)     # the round's own cached posterior
        self.rounds.append(Round(
            task=task, Y=np.array(Y, np.float64), mask=np.array(mask),
            params=tuple(state.params), alpha=post.alpha,
            mean=np.asarray(mean), var=np.asarray(var),
            sweeps=post.solve_info.iters if post.solve_info is not None
            else None))


def cycle_of(kind: str):
    """``cycle(cell, k)`` of the traffic kind ``kind``, found by name in
    ``perfbench/kinds/<kind>.py``: a later kind is a new file there."""
    path = Path(__file__).resolve().parent / "kinds" / f"{kind}.py"
    if not path.is_file():
        raise SystemExit(f"unknown traffic kind {kind!r}: no {path}")
    spec = importlib.util.spec_from_file_location(f"perfbench_kind_{kind}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.cycle


def run_window(cell: Cell, seconds: float, first_cycle: int = 0,
               on_cycle=None) -> tuple[float, float, int]:
    """Whole cycles until ``seconds`` have passed: (start, end, cycles)."""
    cycle = cycle_of(cell.traffic["kind"])
    start = time.perf_counter()
    k = first_cycle
    while True:
        cycle(cell, k)
        k += 1
        if on_cycle is not None:
            on_cycle(k - first_cycle)
        end = time.perf_counter()
        if end - start >= seconds:
            return start, end, k - first_cycle
