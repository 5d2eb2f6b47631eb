"""Tests of the readers of the program's own spans and counters
(``repro.core.telemetry``) on hand-built contexts, and a pin of every
reading of the recorded trace, so that a change to the trace reduction
shows if it moves an accepted metric."""
from __future__ import annotations

import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest
from repro.core import telemetry

from perfbench import harness, run, trace

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "mvm.xplane.pb"
KERNEL = r"lk_mvm_fused\S* = .*tpu_custom_call"


def _window(rounds: int, step):
    """A window of ``rounds`` rounds, each running ``step()``; a span and a
    counter recorded after the window must not be read."""
    spans = harness.Spans()
    start = time.perf_counter()
    for _ in range(rounds):
        a = time.perf_counter()
        step()
        spans.records.append(("round", a, time.perf_counter()))
    end = time.perf_counter()
    with telemetry.span("posterior.solve"), telemetry.span("solver.dispatch"):
        telemetry.count("jax.compile_s", 100.0)
        telemetry.count("sync.after_window")
    return SimpleNamespace(spans=spans, window=(start, end), window_rounds=[],
                           trace=None, config={}, device_kind="TPU v5 lite")


def _solve_round():
    with telemetry.span("posterior.solve"):
        with telemetry.span("solver.dispatch"):
            time.sleep(0.002)
        time.sleep(0.003)


def test_solve_readers_are_per_round_sums():
    ctx = _window(3, _solve_round)
    solve = run.reader("solve_s")(ctx)
    dispatch = run.reader("solve_dispatch_s")(ctx)
    assert solve == pytest.approx(sum(telemetry.durations(
        "posterior.solve", *ctx.window)) / 3)
    assert dispatch == pytest.approx(sum(telemetry.durations(
        "solver.dispatch", *ctx.window)) / 3)
    assert 0.002 <= dispatch < solve < 1.0
    assert solve >= 0.005


def test_counter_readers_are_per_round_sums():
    def step():
        telemetry.count("jax.compile_s", 0.25)
        telemetry.host_read([1.0], "t.reader")
        telemetry.count("sync.t.other", 2)

    ctx = _window(4, step)
    assert run.reader("compile_s")(ctx) == pytest.approx(0.25)
    assert run.reader("host_syncs")(ctx) == pytest.approx(3.0)


def test_readers_with_nothing_to_read():
    ctx = _window(2, lambda: None)
    assert run.reader("solve_s")(ctx) is None
    assert run.reader("solve_dispatch_s")(ctx) is None
    assert run.reader("compile_s")(ctx) == 0.0
    assert run.reader("host_syncs")(ctx) == 0.0
    empty = _window(0, lambda: None)
    for name in ("solve_s", "solve_dispatch_s", "compile_s", "host_syncs"):
        assert run.reader(name)(empty) is None


def test_readers_on_a_program_without_telemetry(monkeypatch):
    ctx = _window(2, _solve_round)
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)
    monkeypatch.delattr(sys.modules["repro.core"], "telemetry")
    for name in ("solve_s", "solve_dispatch_s", "compile_s", "host_syncs"):
        assert run.reader(name)(ctx) is None


def test_recorded_trace_reads_as_accepted():
    tr = trace.load(str(FIXTURE))
    assert tr.window == (45479435, 59348993)
    assert trace.busy_s(tr) == pytest.approx(0.000104904, rel=1e-9)
    assert trace.kernel_calls(tr, KERNEL) == [
        (pytest.approx(1.6765e-05, rel=1e-9), 17),
        (pytest.approx(6.4117e-05, rel=1e-9), 65)]
    top = trace.top_ops(tr)
    assert [name for name, _ in top] == [
        "%lk_mvm_fused.1 f32[65,256,52]", "%lk_mvm_fused.1 f32[17,256,52]",
        "%copy.1 f32[65,256,52]", "%copy.4 f32[65,256,52]",
        "%copy.1 f32[17,256,52]", "%copy.4 f32[17,256,52]",
        "%broadcast_multiply_fusion f32[65,256,52]", "%copy f32[256,52]",
        "%broadcast_multiply_fusion f32[17,256,52]",
        "%copy-done f32[256,256]"]
    assert [s for _, s in top] == pytest.approx(
        [6.4117e-05, 1.6765e-05, 7.794e-06, 7.678e-06, 2.549e-06, 2.526e-06,
         1.495e-06, 1.224e-06, 5.37e-07, 1.5e-07], rel=1e-9)
    gaps = trace.idle_gaps(tr)
    assert [lab for lab, _ in gaps[:3]] == ["outside spans", "update",
                                            "predict"]
    assert [s for _, s in gaps[:3]] == pytest.approx(
        [0.012170495, 0.00107671, 0.000517435], rel=1e-9)
    ctx = SimpleNamespace(trace=tr, config={"n": 256, "m": 52},
                          device_kind="TPU v5 lite")
    assert run.reader("mvm_roofline_pct")(ctx) == pytest.approx(
        20.759261015436678, rel=1e-9)
    assert run.reader("device_idle_pct")(ctx) == pytest.approx(
        99.24363847788084, rel=1e-9)
