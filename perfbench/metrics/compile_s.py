"""Seconds per round of JAX tracing, lowering and building or loading
executables inside the window: the program's counter ``jax.compile_s``
(``repro.core.telemetry``, fed by ``jax.monitoring``), summed over the
window and divided by its rounds. Zero when nothing compiles again. A
program without that module reads nothing."""


def read(ctx):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    rounds = ctx.spans.durations("round", since=ctx.window[0])
    if not rounds:
        return None
    return telemetry.total("jax.compile_s", since=ctx.window[0],
                           until=ctx.window[1]) / len(rounds)
