"""Deliberate device-to-host reads per round: the program's counters
``sync.*`` (``repro.core.telemetry.host_read``), summed over the window
and divided by its rounds. A program without that module reads
nothing."""


def read(ctx):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    rounds = ctx.spans.durations("round", since=ctx.window[0])
    if not rounds:
        return None
    return telemetry.total("sync.*", since=ctx.window[0],
                           until=ctx.window[1]) / len(rounds)
