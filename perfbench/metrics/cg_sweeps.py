"""Operator sweeps of each round's posterior solve
(``posterior(state).solve_info.iters``), mean over the window's rounds."""
import numpy as np


def read(ctx):
    s = [int(r.sweeps) for r in ctx.window_rounds if r.sweeps is not None]
    return float(np.mean(s)) if s else None
