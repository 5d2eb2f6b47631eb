"""Seconds per round in the posterior layer: the span around the final-value
prediction (``posterior(state).final()``), closed when the
values are on the host. Mean over the window's rounds."""
import numpy as np


def read(ctx):
    d = ctx.spans.durations("predict", since=ctx.window[0])
    return float(np.mean(d)) if d else None
