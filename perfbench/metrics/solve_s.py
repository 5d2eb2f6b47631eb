"""Seconds per round in the posterior's solve: the program's own span
``posterior.solve`` (``repro.core.telemetry``) around the engine solve of
``final()``, from dispatch to the guard's verdict on the finished loop.
Summed over the window and divided by its rounds. A program without that
module reads nothing."""


def read(ctx):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    rounds = ctx.spans.durations("round", since=ctx.window[0])
    d = telemetry.durations("posterior.solve", since=ctx.window[0],
                            until=ctx.window[1])
    return sum(d) / len(rounds) if rounds and d else None
