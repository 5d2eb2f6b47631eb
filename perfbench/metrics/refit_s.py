"""Seconds per refit: the program's own spans ``state.refit``
(``repro.core.telemetry``) inside the window, summed and divided by their
number. A refit reads the polish's result on the host, so its span holds
the refit's device time. A window without a refit, or a program without
that module, reads nothing."""


def read(ctx):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    d = telemetry.durations("state.refit", since=ctx.window[0],
                            until=ctx.window[1])
    return sum(d) / len(d) if d else None
