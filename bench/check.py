"""How ``correct`` is decided: every answer of the window against a plain
float64 reference, and the control that the comparison has to fail.

The reference is the configuration's ``forward`` (``configs/<arch>.py``) in
NumPy float64 with a SciPy sparse ``Â``: it imports nothing of the program
and takes from the run only the benchmark's own graph, weights and request
features.  The number compared is ``logit_err``: over every request answered,
the largest ``max |logits - reference| / max |reference|``.

The control is the same reference in the program's place, computed on the
device one precision below what the configuration states: float32 products
at ``highest`` become three bfloat16 passes (``high``), as written out in
``dot3``.
"""
from __future__ import annotations

import numpy as np


def reference(s):
    """``idx -> float64 logits`` of pool entry ``idx``, computed once each."""
    import scipy.sparse as sp
    g = s.graph
    a = sp.csr_matrix((g.vals.astype(np.float64), (g.rows, g.cols)),
                      shape=(g.n, g.n))
    w = {k: np.asarray(v, np.float64) for k, v in s.weights.items()}
    memo: dict[int, np.ndarray] = {}

    def ref(idx: int) -> np.ndarray:
        if idx not in memo:
            memo[idx] = s.arch.forward(
                lambda x: a @ x, np.dot, lambda x: np.maximum(x, 0.0),
                s.pool[idx].astype(np.float64), w)
        return memo[idx]
    return ref


def judge(s, recs) -> dict:
    """The numbers compared, each with its limit."""
    ref = reference(s)
    err = 0.0
    answered = [r for r in recs if r.logits is not None]
    for r in answered:
        want = ref(r.idx)
        gap = np.max(np.abs(np.asarray(r.logits, np.float64) - want))
        err = max(err, float(gap / np.max(np.abs(want))))
    if not answered:
        err = float("inf")
    return {"failed": {"value": len(recs) - len(answered), "limit": 0},
            "logit_err": {"value": err, "limit": s.cfg["logit_err_limit"]}}


def verdict(checks: dict) -> bool:
    """``correct``: every number compared lies within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def dot3(a, b):
    """``a @ b`` in three bfloat16 passes with float32 accumulation: each
    operand split into a bfloat16 head and tail, the tail-by-tail product
    dropped (what ``Precision.HIGH`` does on a TPU).  The head is rounded
    to nearest even on the float32 bits, so that no compiler can fold the
    split away as excess precision."""
    import jax.numpy as jnp
    from jax import lax

    def split(x):
        bits = lax.bitcast_convert_type(x, jnp.uint32)
        bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
        hi = lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
        return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)

    def d(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32)
    return d(ah, bh) + (d(ah, bl) + d(al, bh))


def control_infer(s):
    """``async (features) -> logits`` of the control, on the device."""
    import jax
    import jax.numpy as jnp
    g = s.graph
    a = jnp.zeros((g.n, g.n), jnp.float32).at[g.rows, g.cols].add(g.vals)

    @jax.jit
    def fwd(a, h, w):
        return s.arch.forward(lambda x: dot3(a, x), dot3, jax.nn.relu, h, w)

    async def infer(h):
        return fwd(a, jnp.asarray(h), s.weights)
    return infer
