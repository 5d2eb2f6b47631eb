"""Seconds per round in the fit layer: the span around a round's update
(a cold ``fit``, or ``extend`` [+ a warm ``refit``]), closed when every
array of the new state is ready. Mean over the window's rounds."""
import numpy as np


def read(ctx):
    d = ctx.spans.durations("update", since=ctx.window[0])
    return float(np.mean(d)) if d else None
