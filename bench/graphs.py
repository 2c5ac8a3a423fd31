"""Synthetic stand-ins for the paper's Table IV datasets, made by the benchmark.

The generator is the one the paper reproduction uses for its datasets: the
Table IV vertex, edge and feature counts, a hub-skewed (Zipf-like) degree
distribution, the GCN renormalised adjacency ``D^-1/2 (A + I) D^-1/2`` and
binary bag-of-words features at the Table IV density.  It is kept here so
that the yardstick does not move when the program's own data module does.

Like the real dataset it stands for, a graph is fixed: it depends on the
configuration's ``graph_seed`` alone, never on a run's ``--seed``.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Graph:
    n: int
    rows: np.ndarray        # int32, sorted by row
    cols: np.ndarray        # int32
    vals: np.ndarray        # float32
    features: np.ndarray    # (n, f) float32 bag-of-words


def _zipf_targets(rng: np.random.Generator, n: int, size: int,
                  skew: float = 2.0) -> np.ndarray:
    u = rng.uniform(size=size)
    return np.minimum((n * u ** skew).astype(np.int64), n - 1)


def _normalized(n: int, src: np.ndarray, dst: np.ndarray):
    rows = np.concatenate([src, np.arange(n, dtype=np.int64)])
    cols = np.concatenate([dst, np.arange(n, dtype=np.int64)])
    deg = np.bincount(rows, minlength=n).astype(np.float32)
    dinv = 1.0 / np.sqrt(np.maximum(deg, 1.0))
    vals = dinv[rows] * dinv[cols]
    order = np.argsort(rows, kind="stable")
    return (rows[order].astype(np.int32), cols[order].astype(np.int32),
            vals[order].astype(np.float32))


def make_graph(dataset: dict) -> Graph:
    """The graph of a configuration's ``dataset`` entry."""
    n, e, f = dataset["vertices"], dataset["edges"], dataset["features"]
    rng = np.random.default_rng(dataset["graph_seed"])
    src = rng.integers(0, n, size=e, dtype=np.int64)
    dst = _zipf_targets(rng, n, e)
    rows, cols, vals = _normalized(n, src, dst)
    nnz = max(1, int(round(n * f * dataset["feature_density"])))
    h = np.zeros((n, f), np.float32)
    h.flat[rng.choice(n * f, size=nnz, replace=False)] = 1.0
    return Graph(n=n, rows=rows, cols=cols, vals=vals, features=h)


def block_count(rows: np.ndarray, cols: np.ndarray, block: int = 8) -> int:
    """Distinct ``block``x``block`` tiles holding a stored entry."""
    return len(np.unique((rows // block).astype(np.int64) * (1 << 32)
                         + cols // block))
