"""Functional compute primitives used by the engine.

The numerical result of a kernel is primitive-independent (GEMM, SpDMM and
SpMM all compute Z = X·Y); the primitive choice decides *time* and *data
movement*.  The engine therefore computes results one of two ways:

- a literal engine (TPU / tests): the Pallas kernels of the route the
  compiled program records (``DynasparseEngine.route``);
- a non-literal engine: a COO segment-sum SpDMM (adjacency is far too large
  to densify) and plain ``jnp.dot`` for dense operands — this module.

``SparseCOO`` is the storage format of the paper's BufferA (Alg. 2).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SparseCOO:
    """COO sparse matrix (rows sorted; the paper's BufferA layout).

    ``tag`` marks the matrix role ("adjacency" / "features" / "generic") —
    used by the benchmark harness's Table V accounting, which must be able to
    exploit adjacency sparsity while treating feature matrices as dense.
    """
    shape: Tuple[int, int]
    rows: jax.Array   # (nnz,) int32
    cols: jax.Array   # (nnz,) int32
    vals: jax.Array   # (nnz,) float
    tag: str = "generic"

    def tree_flatten(self):
        return (self.rows, self.cols, self.vals), (self.shape, self.tag)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        shape, tag = aux
        rows, cols, vals = leaves
        return cls(shape, rows, cols, vals, tag)

    @property
    def nnz(self) -> int:
        return int(self.rows.shape[0])

    @property
    def density(self) -> float:
        return self.nnz / (self.shape[0] * self.shape[1])

    def todense(self) -> np.ndarray:
        out = np.zeros(self.shape, dtype=np.asarray(self.vals).dtype)
        np.add.at(out, (np.asarray(self.rows), np.asarray(self.cols)),
                  np.asarray(self.vals))
        return out

    def row_stripe_density(self, tile_m: int, eps: float = 0.0) -> np.ndarray:
        """α(X_{i,:}) per row-stripe, from nnz counts (host, O(nnz)).

        ``eps > 0`` drops stored values with ``|v| <= eps`` from the count,
        matching the dense :func:`repro.core.sparsity.stripe_density`
        tolerance; ``eps == 0`` counts every stored entry (nnz semantics).
        """
        n_stripes = -(-self.shape[0] // tile_m)
        rows = np.asarray(self.rows)
        if eps > 0.0:
            rows = rows[np.abs(np.asarray(self.vals)) > eps]
        counts = np.bincount(rows // tile_m,
                             minlength=n_stripes).astype(np.float64)
        sizes = np.full(n_stripes, tile_m * self.shape[1], dtype=np.float64)
        tail = self.shape[0] - (n_stripes - 1) * tile_m
        sizes[-1] = tail * self.shape[1]
        return counts / sizes


@functools.partial(jax.jit, static_argnames=("n_rows", "chunk"))
def coo_spdmm(rows: jax.Array, cols: jax.Array, vals: jax.Array,
              h: jax.Array, n_rows: int, chunk: int = 1_000_000) -> jax.Array:
    """Z = A @ H with A in COO — scatter-gather SpDMM (paper Alg. 2).

    Gather (Pairing Unit): ``h[cols]``; Update (Multiply Unit): ``vals * h``;
    Reduce (Accumulator): ``segment_sum`` into output rows.  Chunked over
    edges with ``lax.scan`` so the gathered intermediate never exceeds
    ``chunk x d`` — the BufferG working-set bound.
    """
    nnz = rows.shape[0]
    d = h.shape[1]
    n_chunks = -(-nnz // chunk)
    if n_chunks <= 1:
        upd = vals[:, None] * h[cols]
        return jax.ops.segment_sum(upd, rows, num_segments=n_rows)

    pad = n_chunks * chunk - nnz
    rows_p = jnp.pad(rows, (0, pad), constant_values=n_rows)  # OOB -> dropped
    cols_p = jnp.pad(cols, (0, pad))
    vals_p = jnp.pad(vals, (0, pad))

    def body(acc, xs):
        r, c, v = xs
        upd = v[:, None] * h[c]
        return acc + jax.ops.segment_sum(upd, r, num_segments=n_rows), None

    acc0 = jnp.zeros((n_rows, d), h.dtype)
    acc, _ = jax.lax.scan(
        body, acc0,
        (rows_p.reshape(n_chunks, chunk), cols_p.reshape(n_chunks, chunk),
         vals_p.reshape(n_chunks, chunk)))
    return acc


def spdmm_exec(a: SparseCOO, h: jax.Array, chunk: int = 1_000_000) -> jax.Array:
    return coo_spdmm(a.rows, a.cols, a.vals, h, n_rows=a.shape[0], chunk=chunk)


def gemm_exec(x: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.dot(x, y, preferred_element_type=jnp.float32)
