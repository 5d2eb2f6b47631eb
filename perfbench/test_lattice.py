"""Tests of the NAS-Bench-201 lattice sampler, its traffic kind, and the
comparison on a lattice task after a refit, on the CPU at small sizes."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from perfbench import check, harness, lattice, run

HERE = Path(__file__).resolve().parent
NB201 = json.loads((HERE / "configs" / "nb201.json").read_text())
LAT = NB201["lattice"]
BIG = 2**31 + 11


def test_edges_of_the_cell():
    assert lattice.edge_list(6) == [(0, 1), (0, 2), (1, 2), (0, 3), (1, 3),
                                    (2, 3)]
    assert lattice.nodes_of(3) == 3
    with pytest.raises(ValueError):
        lattice.nodes_of(5)


def test_cells_are_distinct_one_hot_and_follow_the_seed():
    a = lattice.sample_task(BIG, 300, 12, LAT)
    b = lattice.sample_task(BIG, 300, 12, LAT)
    c = lattice.sample_task(BIG + 1, 300, 12, LAT)
    assert a.X.shape == (300, 30)
    assert set(np.unique(a.X)) == {0.0, 1.0}
    assert (a.X.sum(axis=1) == 6).all()
    assert (a.X.reshape(300, 6, 5).sum(axis=2) == 1).all()
    assert len({row.tobytes() for row in a.X}) == 300
    for u, v in zip(a, b):
        assert (u == v).all()
    assert not (a.X == c.X).all()
    assert (a.mask.sum(axis=1) == 12).sum() >= 1       # one curve complete
    assert ((a.Y_full >= 0) & (a.Y_full <= 1)).all()


def test_the_whole_lattice_can_be_drawn():
    t = lattice.sample_task(3, 125, 4, dict(LAT, edges=3))
    assert len({row.tobytes() for row in t.X}) == 125


def test_drivers_follow_the_cell():
    ops = LAT["ops"]
    op = {o: ops.index(o) for o in ops}
    cells = np.array([
        [op["nor_conv_3x3"]] * 6,
        [op["none"]] * 6,
        # only 0->1 conv and 1->3 skip: a parametrised path 0->1->3
        [op["nor_conv_1x1"], op["none"], op["none"], op["none"],
         op["skip_connect"], op["none"]],
        # conv 0->1 but 1 leads nowhere; 0->3 a pool: no parametrised path
        [op["nor_conv_3x3"], op["none"], op["none"], op["avg_pool_3x3"],
         op["none"], op["none"]],
    ])
    drv = lattice.drivers(cells, ops)
    np.testing.assert_allclose(drv, [[1, 0, 1, 0], [0, 0, 0, 1],
                                     [0, 1 / 6, 1, 4 / 6],
                                     [1 / 6, 0, 0, 4 / 6]])


def test_stream_lattice_puts_the_lattice_tasks_in_place(f32_cpu):
    spec = run.spec_of(HERE / "configs" / "nb201.json", "stream_lattice")
    assert spec.traffic["kind"] == "stream_lattice"
    cfg = dict(spec.config, n=24, m=6)
    cell = harness.Cell(cfg, dict(spec.traffic, cycle_rounds=1), BIG)
    cell.make_tasks()
    want = lattice.make_tasks(cfg, spec.traffic, BIG)
    harness.cycle_of("stream_lattice")(cell, 0)
    assert len(cell.tasks) == spec.traffic["tasks"]
    for got, w in zip(cell.tasks, want):
        assert (got.X == w.X).all() and (got.Y_full == w.Y_full).all()
    assert len(cell.rounds) == 1 and cell.rounds[0].task == 0


def test_stream_and_stream_lattice_are_one_mix():
    a = json.loads((HERE / "traffic" / "stream.json").read_text())
    b = json.loads((HERE / "traffic" / "stream_lattice.json").read_text())
    assert a.pop("kind") == "stream" and b.pop("kind") == "stream_lattice"
    assert a == b and a["refit_every"] == 4


def test_lattice_round_after_a_refit_is_within_the_limits(f32_cpu):
    """A seeded 5^3-lattice task (125 x 24, d 15): fit, an epoch more,
    refit, ``final()``; the program's round is inside ``nb201.json``'s
    limits and the control fails one of them."""
    from repro import core

    task = lattice.sample_task(BIG, 125, 24, dict(LAT, edges=3))
    gp = NB201["gp"]
    cfg = core.LKGPConfig(**gp)
    mask = task.mask.copy()
    st = core.fit(task.X, task.t, task.Y, mask, cfg)
    seen = mask.sum(axis=1).astype(int)
    grow = np.nonzero(seen < mask.shape[1])[0]
    mask[grow, seen[grow]] = 1.0
    Y = np.where(mask > 0, task.Y_full, 0.0)
    st = core.refit(core.extend(st, Y, mask))
    assert st.fit_result.optimizer == "polish"
    mean, var = core.posterior(st).final()
    rnd = harness.Round(task=0, Y=Y, mask=mask, params=tuple(st.params),
                        alpha=core.posterior(st).alpha,
                        mean=np.asarray(mean), var=np.asarray(var),
                        sweeps=None)
    engine = core.get_engine(gp["backend"])
    limits = NB201["limits"]
    got = check.compare_round(rnd, task.X, task.t, gp, 5, engine)
    assert all(np.isfinite(v) and v <= limits[k] for k, v in got.items()), got
    ctl = check.compare_round(rnd, task.X, task.t, gp, 5, control=True)
    assert any(v > limits[k] for k, v in ctl.items()), ctl


@pytest.fixture
def f32_cpu(monkeypatch, tmp_path):
    """The chip's dtype (x64 off), restored afterwards."""
    import jax
    saved = jax.config.jax_enable_x64
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", saved)
