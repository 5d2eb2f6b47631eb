"""Seconds per round the host spends handing the posterior's solve to the
device: the program's own span ``solver.dispatch``
(``repro.core.telemetry``), the base solver's call up to its unfinished
loop (trace, lowering, executable cache, launch). Summed over the window
and divided by its rounds. A program without that module reads nothing."""


def read(ctx):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    rounds = ctx.spans.durations("round", since=ctx.window[0])
    d = telemetry.durations("solver.dispatch", since=ctx.window[0],
                            until=ctx.window[1])
    return sum(d) / len(rounds) if rounds and d else None
