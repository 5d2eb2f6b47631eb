"""Tests of the benchmark harness, on the CPU at small sizes.

They check that every name in ``BENCHMARK.json`` finds its files, the
work arithmetic of the roofline, the trace reduction on a trace recorded
on a TPU v5e, that the comparison passes the program and fails the
control, and that a run with the timed path broken underneath comes out
not correct.
"""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from perfbench import check, faults, harness, roofline, run, trace

ROOT = Path(__file__).resolve().parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
HERE = Path(__file__).resolve().parent
FIXTURE = HERE / "fixtures" / "mvm.xplane.pb"
# the committed mix, and the same tenant refitting every 2nd round, as a
# refitting stream mix would (the service's default is every 4th; here a
# 4-round cycle has to hold one)
MIXES = {"stream_fixed": {}, "stream": {"refit_every": 2}}
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


@pytest.fixture
def f32_cpu(monkeypatch, tmp_path):
    """The chip's dtype (x64 off); the run leaves the process's compile
    cache settings as it found them and writes nothing into the checkout."""
    import jax
    keys = ("jax_enable_x64", "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_enable_x64", False)
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_name_finds_its_files(workload):
    spec = run.load_spec(workload)
    cfg = spec.config
    assert {"n", "m", "d", "gp", "limits", "assumed"} <= set(cfg)
    assert set(cfg["limits"]) == set(check.NUMBERS)
    assert callable(harness.cycle_of(spec.traffic["kind"]))
    names = {m["name"] for m in spec.end_to_end}
    assert {"setup_s", "round_s"} <= names
    assert spec.per_layer
    for m in spec.per_layer:
        assert callable(run.reader(m["name"]))


def test_configs_state_their_cuts():
    listed = {c["name"]: c for c in BENCH["configs"]}
    for path in sorted((HERE / "configs").glob("*.json")):
        cfg = json.loads(path.read_text())
        assert path.stem == cfg["name"]
        assert set(cfg["limits"]) == set(check.NUMBERS)
        for key in cfg["reduced"]:
            assert key in cfg["reduced_why"] and key in cfg["published"]
        if cfg["name"] in listed:
            c = listed[cfg["name"]]
            assert ROOT / c["file"] == path
            assert sorted(cfg["reduced"]) == sorted(c["reduced"])


def test_unknown_traffic_kind_is_refused():
    with pytest.raises(SystemExit, match="unknown traffic kind"):
        harness.cycle_of("no_such_kind")


def test_tasks_follow_the_seed():
    spec = _small("stream_fixed")
    big = 2**31 + 11
    a, b, c = (harness.Cell(spec.config, spec.traffic, s)
               for s in (big, big, big + 1))
    for cell in (a, b, c):
        cell.make_tasks()
    assert len(a.tasks) == spec.traffic["tasks"]
    for x, y in zip(a.tasks, b.tasks):
        for u, v in zip(x, y):
            assert (u == v).all()
    assert not (a.tasks[0].Y_full == c.tasks[0].Y_full).all()
    # every unfinished curve can still gain epochs: a stream round has work
    assert all((t.mask.sum(axis=1) < t.mask.shape[1]).any() for t in a.tasks)


def test_mvm_work_arithmetic():
    assert roofline.lk_mvm_flops(17, 2000, 52) == 2.0 * 17 * (
        2000 * 2000 * 52 + 2000 * 52 * 52)
    assert roofline.lk_mvm_bytes(1, 4, 2) == 4.0 * (16 + 4 + 8 + 24)
    # 2000 x 52 at B = 65: about 245 FLOP/B, just over v5e's ridge of 240
    s, bound = roofline.least_seconds(roofline.lk_mvm_flops(65, 2000, 52),
                                      roofline.lk_mvm_bytes(65, 2000, 52),
                                      "TPU v5 lite")
    assert bound == "compute"
    assert s == pytest.approx(roofline.lk_mvm_flops(65, 2000, 52) / 197e12)
    assert roofline.least_seconds(1.0, 1e9, "TPU v5 lite")[1] == "memory"
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_trace_reduction_on_recorded_trace():
    tr = trace.load(str(FIXTURE))
    assert tr.window is not None and tr.devices
    busy = trace.busy_s(tr)
    assert 0.0 < busy < tr.window_s
    calls = trace.kernel_calls(tr, r"lk_mvm_fused\S* = .*tpu_custom_call")
    assert sorted({b for _, b in calls}) == [17, 65]
    assert all(s > 0 for s, _ in calls)
    labels = {lab for lab, _ in trace.idle_gaps(tr)}
    assert labels <= {"update", "predict", "outside spans"}
    top = trace.top_ops(tr)
    assert 0 < len(top) <= 10
    assert sum(s for _, s in top) <= busy * (1 + 1e-9) + 1e-12


def test_no_chip_no_result(capsys):
    assert run.main(["--workload", WORKLOADS[0], "--seed", "1",
                     "--seconds", "1"]) == 3
    out = capsys.readouterr().out
    assert not out.strip()


def _small(mix: str, n: int = 24, m: int = 8):
    spec = run.spec_of(HERE / "configs" / "lcbench.json", "stream_fixed")
    spec.config = dict(spec.config, n=n, m=m)
    spec.traffic = dict(spec.traffic, tasks=2, **MIXES[mix])
    return spec


def _run(spec, seed=2**31 + 11):
    import jax
    return run.run(spec, seed, 0.0, False, jax.devices()[:1],
                   device_kind="TPU v5 lite")


@pytest.mark.parametrize("mix", MIXES)
def test_program_passes_and_control_fails(mix, f32_cpu):
    spec = _small(mix)
    cell = harness.Cell(spec.config, spec.traffic, 5)
    cell.make_tasks()
    harness.run_window(cell, 0.0)
    args = (cell.rounds, cell.tasks, spec.config["gp"], spec.config["limits"],
            5)
    ok, program = check.compare(*args)
    assert ok, program
    ok, control = check.compare(*args, control=True)
    assert not ok
    assert control["mvm_err"][0] > control["mvm_err"][1]
    # the round's own mean, from the timed path, reads the lower precision:
    # at the cell's size past its limit, here ten times the program's gap
    assert control["mean_gap"][0] > 10 * program["mean_gap"][0]


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.parametrize("fault", faults.FAULTS)
def test_broken_timed_path_is_not_correct(fault, mix, f32_cpu):
    with faults.planted(fault):
        out = _run(_small(mix))
    assert not out["correct"], out["checks"]
