"""Seeded learning-curve tasks on a categorical cell lattice (NAS-Bench-201).

The benchmark's own copy: it imports nothing of the program. A NAS-Bench-201
cell (Dong & Yang 2020, arXiv 2001.00326) is a DAG on 4 nodes whose 6
edges, ``j <- i`` for i < j in the order (1<-0), (2<-0), (2<-1), (3<-0),
(3<-1), (3<-2), each carry one of 5 operations. A configuration is its
one-hot code, (edges x ops) coordinates with one 1 per edge.

The curves are ``perfbench/curves.py``'s families. Their four driving
coordinates are functions of the cell, each in [0, 1] (``assumed`` in the
configuration):

0. the share of edges that are ``nor_conv_3x3`` (asymptote, rate, noise);
1. the share of edges that are ``nor_conv_1x1`` (asymptote, start);
2. 1 if a path of operations other than ``none`` links input to output
   through at least one convolution, else 0 (rate, pow3 exponent);
3. the share of edges that are ``none`` (delay).

Noise, spikes, divergent curves and censoring are those of
``curves.sample_task``. Everything is numpy and seeded: the same seed gives
the same task.
"""
from __future__ import annotations

import math

import numpy as np

from . import curves

CONV = ("nor_conv_1x1", "nor_conv_3x3")


def nodes_of(edges: int) -> int:
    """Nodes of the complete DAG with ``edges`` edges."""
    k = int(round((1 + math.sqrt(1 + 8 * edges)) / 2))
    if k * (k - 1) // 2 != edges:
        raise ValueError(f"{edges} edges make no complete DAG")
    return k


def edge_list(edges: int) -> list[tuple[int, int]]:
    """(source, target) of each edge, in NAS-Bench-201's order."""
    k = nodes_of(edges)
    return [(i, j) for j in range(1, k) for i in range(j)]


def sample_cells(rng: np.random.Generator, n: int, edges: int,
                 n_ops: int) -> np.ndarray:
    """(n, edges) operation indices of ``n`` distinct cells, drawn without
    replacement from the ``n_ops ** edges`` of the lattice."""
    idx = rng.choice(n_ops ** edges, size=n, replace=False)
    return np.stack([(idx // n_ops ** e) % n_ops for e in range(edges)],
                    axis=1)


def one_hot(cells: np.ndarray, n_ops: int) -> np.ndarray:
    """(n, edges * n_ops) one-hot code, edge-major."""
    n, edges = cells.shape
    X = np.zeros((n, edges, n_ops))
    X[np.arange(n)[:, None], np.arange(edges)[None, :], cells] = 1.0
    return X.reshape(n, edges * n_ops)


def drivers(cells: np.ndarray, ops: list[str]) -> np.ndarray:
    """(n, 4) curve coordinates of each cell (see the module docstring)."""
    n, edges = cells.shape
    share = lambda op: np.mean(cells == ops.index(op), axis=1)  # noqa: E731
    conv = np.isin(cells, [ops.index(o) for o in CONV])
    live = cells != ops.index("none")
    # reach[:, v]: 0 unreachable, 1 reached without a convolution, 2 with
    reach = np.zeros((n, nodes_of(edges)), np.int64)
    reach[:, 0] = 1
    for e, (i, j) in enumerate(edge_list(edges)):
        via = np.where(conv[:, e], 2, reach[:, i])
        reach[:, j] = np.maximum(reach[:, j],
                                 np.where(live[:, e] & (reach[:, i] > 0),
                                          via, 0))
    return np.stack([share("nor_conv_3x3"), share("nor_conv_1x1"),
                     (reach[:, -1] == 2).astype(np.float64), share("none")],
                    axis=1)


def sample_task(seed: int, n: int, m: int, lattice: dict,
                observed_fraction: tuple[float, float] = (0.1, 0.9),
                noise: float = 0.01, spike_prob: float = 0.05,
                diverge_prob: float = 0.03) -> curves.Task:
    """One task: ``n`` distinct cells x ``m`` epochs, censored at random.

    Each curve is observed up to a length drawn from ``observed_fraction``
    of ``m``; one curve is complete.
    """
    ops, edges = list(lattice["ops"]), lattice["edges"]
    rng = np.random.default_rng(seed)
    cells = sample_cells(rng, n, edges, len(ops))
    X = one_hot(cells, len(ops))
    drv = drivers(cells, ops)
    t = np.arange(1.0, m + 1.0)
    t_norm = (t - t[0]) / (t[-1] - t[0])
    Y = np.stack([curves._curve(rng, drv[i], t_norm) for i in range(n)])
    Y = Y + rng.normal(0, noise * (0.5 + drv[:, :1]), Y.shape)
    spikes = rng.random(Y.shape) < spike_prob
    Y = np.where(spikes, Y - rng.uniform(0.05, 0.3, Y.shape), Y)
    for i in np.where(rng.random(n) < diverge_prob)[0]:
        start = rng.integers(m // 2, m)
        Y[i, start:] -= np.linspace(0, 0.3, m - start)
    Y = np.clip(Y, 0.0, 1.0)
    lens = rng.integers(max(1, int(observed_fraction[0] * m)),
                        max(2, int(observed_fraction[1] * m)) + 1, n)
    lens[rng.integers(0, n)] = m
    mask = (np.arange(m)[None, :] < lens[:, None]).astype(np.float64)
    return curves.Task(X=X, t=t, Y=Y * mask, mask=mask, Y_full=Y)


def make_tasks(config: dict, traffic: dict, seed: int) -> list[curves.Task]:
    """The cell's tasks from the seed, as ``harness.Cell.make_tasks`` draws
    its task seeds, on the configuration's ``lattice``."""
    rng = np.random.default_rng(seed)
    seeds = rng.integers(0, 2**31 - 1, size=traffic["tasks"])
    frac = tuple(traffic.get("observed_fraction", (0.1, 0.9)))
    return [sample_task(int(s), config["n"], config["m"], config["lattice"],
                        observed_fraction=frac) for s in seeds]
