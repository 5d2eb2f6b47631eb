"""Traffic kind ``stream``: one tenant streaming its learning curves.

Each round makes the calls ``PredictionService.observe`` makes for one
tenant (``extend``, and a warm ``refit`` every ``refit_every``-th round
unless that is 0, the service's "never") and then
``posterior(state).final()``. Every unfinished curve gains one epoch a
round; when every curve is complete the next task starts with a cold
``fit``. A cycle is ``cycle_rounds`` rounds.

Spans: ``update`` (``fit``, or ``extend`` [+ ``refit``]) closes when every
array of the new state is ready; ``predict`` closes when the final-epoch
mean and variance are on the host; ``round`` covers both and the glue.
"""
from __future__ import annotations

import time

import jax
import numpy as np
from repro import core


class _Tenant:
    def __init__(self):
        self.task = -1
        self.state = None
        self.mask = None
        self.observes = 0


def cycle(cell, k: int):
    """``cycle_rounds`` rounds of the tenant (``k`` is not used: the tenant
    carries on where the last cycle left it)."""
    tr = cell.traffic
    if cell.carry is None:
        cell.carry = _Tenant()
    st = cell.carry
    for _ in range(tr["cycle_rounds"]):
        t0 = time.perf_counter()
        if st.state is None:
            st.task = (st.task + 1) % len(cell.tasks)
            task = cell.tasks[st.task]
            st.mask = task.mask.copy()
            Y = np.where(st.mask > 0, task.Y_full, 0.0)
            with cell.spans("update"):
                with cell.spans("fit"):
                    st.state = core.fit(task.X, task.t, Y, st.mask, cell.gp)
                jax.block_until_ready(st.state)
            st.observes = 0
        else:
            task = cell.tasks[st.task]
            with cell.spans("glue"):
                seen = st.mask.sum(axis=1).astype(np.int64)
                grow = np.nonzero(seen < task.t.shape[0])[0]
                st.mask[grow, seen[grow]] = 1.0
                Y = np.where(st.mask > 0, task.Y_full, 0.0)
            with cell.spans("update"):
                with cell.spans("extend"):
                    st.state = core.extend(st.state, Y, st.mask)
                st.observes += 1
                if tr["refit_every"] and st.observes % tr["refit_every"] == 0:
                    with cell.spans("refit"):
                        st.state = core.refit(st.state)
                jax.block_until_ready(st.state)
        with cell.spans("predict"):
            mean, var = core.posterior(st.state).final()
            mean, var = np.asarray(mean), np.asarray(var)
        cell.keep(st.task, st.state, Y, st.mask, mean, var)
        cell.spans.records.append(("round", t0, time.perf_counter()))
        if st.mask.all():
            st.state = None
