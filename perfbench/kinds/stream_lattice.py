"""Traffic kind ``stream_lattice``: the ``stream`` kind on tasks drawn from
the configuration's categorical ``lattice`` (``perfbench/lattice.py``).

``harness.Cell.make_tasks`` draws configurations uniformly from the unit
cube. Before the first round this kind puts the lattice's tasks, drawn
from the same seed, in their place; every round is then ``stream``'s: the
calls ``PredictionService.observe`` makes for one tenant (``extend``, a
warm ``refit`` every ``refit_every``-th round) and
``posterior(state).final()``, with the same spans.
"""
from __future__ import annotations

from perfbench import harness, lattice

_stream = harness.cycle_of("stream")


def cycle(cell, k: int):
    """``stream``'s cycle, on the lattice's tasks."""
    if cell.carry is None:        # no round yet: the tenant has no state
        cell.tasks = lattice.make_tasks(cell.config, cell.traffic, cell.seed)
    _stream(cell, k)
