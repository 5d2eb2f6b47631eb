"""The program's spans and counters (``repro.core.telemetry``).

Spans nest and order on one clock; the ring is bounded while counter
totals stay exact, also under threads; the solve and escalation tallies
read the module; nothing is recorded from inside a jit trace; a profiler
trace holds the program's spans inside the benchmark's window; the lint
sees a read routed through ``host_read`` as a host sync.
"""
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro import core
from repro.analysis import analyze_source
from repro.core import engines, telemetry
from repro.data import sample_task


def _names(since):
    return [r[0] for r in sorted(telemetry._records("*", since, np.inf),
                                 key=lambda r: r[1])]


def test_spans_nest_and_order():
    t0 = time.perf_counter()
    with telemetry.span("t.outer"):
        with telemetry.span("t.inner"):
            time.sleep(0.002)
        with telemetry.span("t.inner"):
            pass
    recs = {r[0]: [] for r in telemetry._records("t.*", t0, np.inf)}
    for name, a, b, by in telemetry._records("t.*", t0, np.inf):
        assert by is None and a <= b
        recs[name].append((a, b))
    (oa, ob), = recs["t.outer"]
    first, second = sorted(recs["t.inner"])
    assert oa <= first[0] <= first[1] <= second[0] <= second[1] <= ob
    assert first[1] - first[0] >= 0.002
    assert _names(t0) == ["t.outer", "t.inner", "t.inner"]
    assert len(telemetry.durations("t.inner", since=t0)) == 2
    assert telemetry.durations("t.inner", since=t0, until=first[1]) == [
        pytest.approx(first[1] - first[0])]
    assert telemetry.durations("t.inner", since=second[1] + 1e-9) == []


def test_span_as_decorator_times_each_call():
    @telemetry.span("t.decorated")
    def f(x):
        return x + 1

    t0 = time.perf_counter()
    assert f(1) == 2 and f(2) == 3
    assert f.__name__ == "f"
    assert len(telemetry.durations("t.decorated", since=t0)) == 2


def test_host_read_counts_and_spans():
    t0 = time.perf_counter()
    before = telemetry.total("sync.t.read")
    out = telemetry.host_read(jax.numpy.arange(3.0), "t.read")
    assert isinstance(out, np.ndarray) and out.tolist() == [0.0, 1.0, 2.0]
    assert telemetry.total("sync.t.read") == before + 1
    assert telemetry.total("sync.t.read", since=t0) == 1
    assert len(telemetry.durations("sync.t.read", since=t0)) == 1
    assert telemetry.snapshot()["sync.t.read"] == before + 1


def test_ring_is_bounded_and_totals_exact():
    before = telemetry.total("t.flood")
    t0 = time.perf_counter()
    for _ in range(telemetry.RING + 10):
        telemetry.count("t.flood")
    assert len(telemetry._log) == telemetry.RING
    # the process total is exact; the window sees only what the ring holds
    assert telemetry.total("t.flood") == before + telemetry.RING + 10
    assert telemetry.total("t.flood", since=t0) == telemetry.RING


def test_counters_exact_under_threads():
    n_threads, n_bumps = 8, 2000
    before = telemetry.total("t.threads")
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def hammer():
            for _ in range(n_bumps):
                telemetry.count("t.threads")
                with telemetry.span("t.threads_span"):
                    pass

        threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    finally:
        sys.setswitchinterval(old)
    assert telemetry.total("t.threads") == before + n_threads * n_bumps


def test_counters_with_a_value_sum_it():
    t0 = time.perf_counter()
    telemetry.count("t.seconds", 0.25)
    telemetry.count("t.seconds", 0.5)
    assert telemetry.total("t.seconds", since=t0) == pytest.approx(0.75)


def test_tallies_read_the_module():
    solves = engines.solve_tally()
    telemetry.count("solver.entries", 3)
    assert engines.solve_tally() == solves + 3
    esc = core.escalation_tally()
    telemetry.count("solver.retry_jitter")
    telemetry.count("solver.strict_failures", 2)
    after = core.escalation_tally()
    assert after["retry_jitter"] == esc["retry_jitter"] + 1
    assert after["strict_failures"] == esc["strict_failures"] + 2
    assert after["switch_solver"] == esc["switch_solver"]
    assert set(after) == {"retry_jitter", "switch_solver", "dense_fallback",
                          "degraded_returns", "strict_failures"}


def test_compile_events_are_counted():
    t0 = time.perf_counter()
    # a shape nothing else in the process compiles
    jax.jit(lambda x: x * 3.0 + 1.0)(np.ones(1237, np.float32))
    assert telemetry.total("jax.traces", since=t0) >= 1
    assert telemetry.total("jax.executables", since=t0) >= 1
    assert telemetry.total("jax.compile_s", since=t0) > 0.0
    assert {"jax.traces", "jax.lowerings", "jax.executables",
            "jax.cache_hits", "jax.compile_s"} <= set(telemetry.snapshot())


def _task(seed=3, n=12, m=6):
    task = sample_task(seed, n, m, 4)
    Y = np.where(task.mask > 0, task.Y_full, 0.0)
    return task, Y


@pytest.mark.parametrize("polish_steps", [2, -1])
def test_fit_records_nothing_from_its_jit_trace(polish_steps):
    task, Y = _task()
    # a cg_max_iters no other test uses: the objective is traced afresh
    cfg = core.LKGPConfig(backend="iterative", polish_steps=polish_steps,
                          lbfgs_iters=2, cg_max_iters=37 + polish_steps)
    solves = engines.solve_tally()
    t0 = time.perf_counter()
    state = core.fit(task.X, task.t, Y, task.mask, cfg)
    assert telemetry.total("jax.traces", since=t0) >= 1   # it did trace
    names = _names(t0)
    assert not [n for n in names if n.startswith("solver.")], names
    assert engines.solve_tally() == solves
    assert names.count("state.fit") == 1
    assert telemetry.total("sync.fit.n_obs", since=t0) == 1
    # the eager posterior solve that follows is spanned and counted once
    t1 = time.perf_counter()
    core.posterior(state).final()
    names = _names(t1)
    for name in ("posterior.engine", "posterior.grams", "posterior.draws",
                 "posterior.solve", "solver.dispatch", "solver.guard",
                 "posterior.products"):
        assert name in names, (name, names)
    assert engines.solve_tally() == solves + 1
    (solve,), (dispatch,) = (telemetry.durations(n, since=t1) for n in
                             ("posterior.solve", "solver.dispatch"))
    assert dispatch <= solve


def test_extend_reads_the_masks_through_host_read():
    task, Y = _task()
    cfg = core.LKGPConfig(backend="iterative", polish_steps=0)
    state = core.fit(task.X, task.t, Y, task.mask, cfg)
    t0 = time.perf_counter()
    core.extend(state, Y, task.mask)
    assert telemetry.total("sync.extend.mask", since=t0) == 2
    assert "state.extend" in _names(t0)


def test_profiler_trace_holds_program_spans_inside_window(tmp_path):
    from jax.profiler import ProfileData

    from perfbench import trace

    task, Y = _task()
    cfg = core.LKGPConfig(backend="iterative", polish_steps=0)
    state = core.fit(task.X, task.t, Y, task.mask, cfg)
    core.posterior(state, cache=False).final()     # compile outside
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("pb.window"):
        mean, _ = core.posterior(state, cache=False).final()
        np.asarray(mean)
    jax.profiler.stop_trace()
    path = str(sorted(tmp_path.rglob("*.xplane.pb"))[-1])
    tr = trace.load(path)
    assert tr.window is not None
    spans = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("lk.")]
    solves = [s for s in spans if s[0] == "lk.posterior.solve"]
    assert len(solves) == 1
    _, a, b = solves[0]
    assert tr.window[0] <= a <= b <= tr.window[1]
    inner = {n for n, x, y in spans if a <= x and y <= b}
    assert {"lk.solver.dispatch", "lk.solver.guard"} <= inner


def test_lint_sees_host_read_in_a_loop():
    src = (
        "import jax\n"
        "from repro.core import telemetry\n"
        "from repro.core.telemetry import host_read\n"
        "def drive(step, x, k):\n"
        "    for _ in range(k):\n"
        "        x = step(x)\n"
        "        telemetry.host_read(x, 'loop')\n"
        "        host_read(x, 'loop')\n"
        "    return x\n")
    found = [f for f in analyze_source(src, "m.py") if f.rule == "RA103"]
    assert len(found) == 2, [f.format() for f in found]
    assert {"telemetry.host_read()", "host_read()"} <= {
        f.message.split(" ")[0] for f in found}
