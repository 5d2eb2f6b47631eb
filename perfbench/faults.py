"""Faults planted in the timed path, to show that the comparison catches them.

Each fault patches the program underneath the harness (the harness itself
is unchanged) and is undone when its context closes:

* ``mean_altered``: one open configuration's final-epoch mean moved by
  5 % of the y scale where ``Posterior.final`` produces it (every mean is
  compared, so one is enough);
* ``var_altered``: every final-epoch variance times 20 there (variances
  are compared on a drawn sample of configurations, so one altered
  variance is caught only when it is drawn);
* ``half_left_out``: half of the configurations' observations after their
  first epoch left out of every fit and extend;
* ``state_unchanged``: ``extend`` returns the state it was given.

``perfbench/test_perfbench.py`` runs each at a small size on the CPU;
``perfbench/readings.py --fault`` reads them at a cell's own size.
"""
from __future__ import annotations

import contextlib
import importlib

import numpy as np

FAULTS = ("mean_altered", "var_altered", "half_left_out", "state_unchanged")


def _drop_half(mask):
    mask = np.array(mask, np.float64)
    keep = mask.copy()
    keep[::2, 1:] = 0.0
    return keep


@contextlib.contextmanager
def planted(fault: str):
    post = importlib.import_module("repro.core.posterior")
    core = importlib.import_module("repro.core")
    saved = [(core, "fit", core.fit), (core, "extend", core.extend),
             (post.Posterior, "final", post.Posterior.final)]
    if fault in ("mean_altered", "var_altered"):
        real = post.Posterior.final

        def final(self, *a, **kw):
            mean, var = real(self, *a, **kw)
            if fault == "var_altered":
                return mean, var * 20.0
            open_rows = np.nonzero(np.asarray(self._state.mask)[:, -1] == 0)[0]
            if open_rows.size == 0:
                return mean, var
            i = int(open_rows[0])
            return mean.at[i].add(0.05 * self._state.y_tf.scale), var
        post.Posterior.final = final
    elif fault == "half_left_out":
        real_fit, real_extend = core.fit, core.extend
        core.fit = lambda X, t, Y, mask, *a, **kw: real_fit(
            X, t, Y, _drop_half(mask), *a, **kw)
        core.extend = lambda st, Y, mask: real_extend(st, Y, _drop_half(mask))
    elif fault == "state_unchanged":
        core.extend = lambda st, Y, mask: st
    else:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    try:
        yield
    finally:
        for obj, name, value in saved:
            setattr(obj, name, value)
