"""Graph datasets — synthetic stand-ins matching the paper's Table IV.

This container is offline, so CiteSeer/Cora/PubMed/Flickr/NELL/Reddit cannot
be downloaded.  We generate graphs with the SAME vertex count, edge count,
feature dimension, class count, adjacency density and input-feature density
as Table IV, with a hub-skewed (Zipf-like) degree distribution so that
per-stripe densities vary the way real scale-free graphs do (which is what
exercises the paper's dynamic per-task decisions).  All generators are
deterministic per dataset name.
"""
from __future__ import annotations

import dataclasses
import functools
import zlib

import jax.numpy as jnp
import numpy as np

from repro.core.primitives import SparseCOO


@dataclasses.dataclass(frozen=True)
class DatasetStats:
    name: str
    vertices: int
    edges: int
    features: int
    classes: int
    density_a: float          # Table IV "Density of A" (self-check only)
    density_h: float          # Table IV "Density of input H"
    hidden: int               # paper §IV-B: 16 for CO/CI/PU else 128


# Table IV, verbatim (Reddit edge count "11x10^7").
DATASETS: dict[str, DatasetStats] = {
    "CO": DatasetStats("CO", 2708, 5429, 1433, 7, 0.0014, 0.0127, 16),
    "CI": DatasetStats("CI", 3327, 4732, 3703, 6, 0.0008, 0.0085, 16),
    "PU": DatasetStats("PU", 19717, 44338, 500, 3, 0.0002, 0.10, 16),
    "FL": DatasetStats("FL", 89250, 899756, 500, 7, 0.0001, 0.46, 128),
    "NE": DatasetStats("NE", 65755, 251550, 61278, 186, 0.000058, 0.0001, 128),
    "RE": DatasetStats("RE", 232965, 110_000_000, 602, 41, 0.0021, 1.0, 128),
}


@dataclasses.dataclass
class Graph:
    stats: DatasetStats
    adj: SparseCOO            # row-normalized adjacency with self-loops
    features: jnp.ndarray | SparseCOO   # dense H, or COO when H is ultra-sparse

    @property
    def features_dense(self) -> jnp.ndarray:
        if isinstance(self.features, SparseCOO):
            return jnp.asarray(self.features.todense())
        return self.features

    @property
    def feature_density(self) -> float:
        if isinstance(self.features, SparseCOO):
            return self.features.density
        h = np.asarray(self.features)
        return float((h != 0).mean())


def _zipf_targets(rng: np.random.Generator, n: int, size: int,
                  skew: float = 2.0) -> np.ndarray:
    """Hub-skewed endpoint sampling: P(v) ∝ rank^-ish via u^skew mapping."""
    u = rng.uniform(size=size)
    return np.minimum((n * u ** skew).astype(np.int64), n - 1)


def _gen_edges(rng: np.random.Generator, n: int, e: int) -> tuple[np.ndarray, np.ndarray]:
    src = rng.integers(0, n, size=e, dtype=np.int64)
    dst = _zipf_targets(rng, n, e)
    return src, dst


def _normalize_adj(n: int, src: np.ndarray, dst: np.ndarray) -> SparseCOO:
    """Â = D^{-1/2} (A + I) D^{-1/2} (GCN renormalization trick)."""
    rows = np.concatenate([src, np.arange(n, dtype=np.int64)])
    cols = np.concatenate([dst, np.arange(n, dtype=np.int64)])
    deg = np.bincount(rows, minlength=n).astype(np.float32)
    dinv = 1.0 / np.sqrt(np.maximum(deg, 1.0))
    vals = dinv[rows] * dinv[cols]
    order = np.argsort(rows, kind="stable")
    return SparseCOO(
        (n, n),
        jnp.asarray(rows[order], jnp.int32),
        jnp.asarray(cols[order], jnp.int32),
        jnp.asarray(vals[order].astype(np.float32)),
        tag="adjacency",
    )


def _gen_features(rng: np.random.Generator, stats: DatasetStats,
                  sparse_threshold: float = 0.01):
    """Bag-of-words-like binary features at the Table IV density.  Ultra-
    sparse feature matrices (NELL: 0.01%) stay in COO to avoid a 65k x 61k
    dense allocation."""
    n, f, d = stats.vertices, stats.features, stats.density_h
    if d >= 1.0:
        return jnp.asarray(rng.normal(size=(n, f)).astype(np.float32))
    nnz = max(1, int(round(n * f * d)))
    if d < sparse_threshold and n * f > 50_000_000:
        rows = rng.integers(0, n, size=nnz, dtype=np.int64)
        cols = rng.integers(0, f, size=nnz, dtype=np.int64)
        order = np.argsort(rows, kind="stable")
        return SparseCOO((n, f), jnp.asarray(rows[order], jnp.int32),
                         jnp.asarray(cols[order], jnp.int32),
                         jnp.asarray(np.ones(nnz, np.float32)),
                         tag="features")
    h = np.zeros((n, f), np.float32)
    idx = rng.choice(n * f, size=nnz, replace=False)
    h.flat[idx] = 1.0
    return jnp.asarray(h)


@functools.lru_cache(maxsize=8)
def load_graph(name: str, scale: float = 1.0) -> Graph:
    """Build the synthetic dataset.  ``scale < 1`` shrinks vertices/edges
    proportionally (density preserved) for CPU-budget functional runs."""
    stats = DATASETS[name]
    if scale != 1.0:
        stats = dataclasses.replace(
            stats,
            vertices=max(64, int(stats.vertices * scale)),
            edges=max(128, int(stats.edges * scale)),
            features=max(16, int(stats.features * min(1.0, scale * 4))),
        )
    # stable across processes (builtin hash() is salted)
    seed = zlib.crc32(f"{name}:{scale}".encode()) % (2**31)
    rng = np.random.default_rng(seed)
    src, dst = _gen_edges(rng, stats.vertices, stats.edges)
    adj = _normalize_adj(stats.vertices, src, dst)
    feats = _gen_features(rng, stats)
    return Graph(stats=stats, adj=adj, features=feats)
