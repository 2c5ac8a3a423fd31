"""Hypothesis-driven property sweeps (optional dev dependency).

``pytest.importorskip`` keeps the tier-1 suite collecting when ``hypothesis``
is absent; the deterministic kernel/layer cases live in ``test_kernels.py``
and ``test_layers.py`` and always run.  The interpret-mode Pallas sweeps are
marked ``slow`` and excluded from the default fast lane (see pytest.ini).
"""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import ops
from repro.kernels.formats import pack_blockcsr
from repro.models.layers import flash_attention


@pytest.mark.slow
@settings(max_examples=25, deadline=None)
@given(
    nrb=st.integers(1, 4), ncb=st.integers(1, 4), nnb=st.integers(1, 3),
    da=st.floats(0.0, 1.0), dy=st.floats(0.0, 1.0),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_sparse_kernels_match_dense(nrb, ncb, nnb, da, dy, seed):
    """Invariant: spdmm/spmm equal the dense product for ANY block pattern."""
    block = 8
    rng = np.random.default_rng(seed)
    m, k, n = nrb * block, ncb * block, nnb * block
    am = (rng.uniform(size=(nrb, ncb)) < da).astype(np.float32)
    ym = (rng.uniform(size=(ncb, nnb)) < dy).astype(np.float32)
    a_dense = (rng.normal(size=(m, k)) * np.kron(am, np.ones((block, block)))
               ).astype(np.float32)
    y_dense = (rng.normal(size=(k, n)) * np.kron(ym, np.ones((block, block)))
               ).astype(np.float32)
    a = pack_blockcsr(a_dense, block)
    y_sp = pack_blockcsr(y_dense, block)
    want = a_dense @ y_dense
    got_spdmm = ops.spdmm(a, jnp.asarray(y_dense), bn=8, interpret=True)
    got_spmm = ops.spmm(a, y_sp, interpret=True)
    np.testing.assert_allclose(np.asarray(got_spdmm), want, rtol=2e-4, atol=2e-3)
    np.testing.assert_allclose(np.asarray(got_spmm), want, rtol=2e-4, atol=2e-3)


@pytest.mark.slow
@settings(max_examples=20, deadline=None)
@given(
    M=st.integers(9, 70), K=st.integers(8, 48), N=st.integers(4, 40),
    tm=st.sampled_from([8, 16, 24, 32]), tn=st.sampled_from([8, 12, 16]),
    dx=st.floats(0.02, 0.9), dy=st.floats(0.02, 1.0),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_compiled_eager_pertask_bit_identity(M, K, N, tm, tn,
                                                      dx, dy, seed):
    """Invariant (ISSUE 4): for ANY ragged geometry and operand sparsity,
    the engine's compiled dispatch is bit-identical to the exact reference
    of its lowering (SpMM tasks as the SpDMM stripe walk), and the per-task
    reference agrees with it within float32 rounding.  A misaligned tile
    (tn=12 splitting N) is refused with ValueError."""
    from repro.core import DynasparseEngine, SparseCOO
    from spmm_reference import pertask_reference, stripe_walk_reference

    rng = np.random.default_rng(seed)
    xd = (rng.normal(size=(M, K)) *
          (rng.uniform(size=(M, K)) < dx)).astype(np.float32)
    yd = (rng.normal(size=(K, N)) *
          (rng.uniform(size=(K, N)) < dy)).astype(np.float32)
    r, c = np.nonzero(xd)
    x = SparseCOO(xd.shape, jnp.asarray(r.astype(np.int32)),
                  jnp.asarray(c.astype(np.int32)),
                  jnp.asarray(xd[r, c]), tag="adjacency")
    eng = DynasparseEngine(tile_m=tm, tile_n=tn, literal=True,
                           interpret=True)
    if tn % 8 and tn < N:
        with pytest.raises(ValueError, match="tile_n"):
            eng.plan(x, jnp.asarray(yd))
        return
    plan = eng.plan(x, jnp.asarray(yd))
    z_c = np.asarray(eng.execute(plan, x, jnp.asarray(yd)))
    z_p = pertask_reference(plan.part, plan.stq, plan.dtq, xd, yd)
    np.testing.assert_array_equal(z_c, stripe_walk_reference(plan, xd, yd))
    np.testing.assert_allclose(z_c, z_p, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(z_c, xd @ yd, rtol=2e-4, atol=2e-3)


@pytest.mark.slow
@settings(max_examples=25, deadline=None)
@given(
    M=st.integers(9, 70), K=st.integers(8, 48), N=st.integers(4, 40),
    tm=st.sampled_from([8, 16, 32]), tn=st.sampled_from([8, 16, 24]),
    bd=st.floats(0.0, 0.6), dy=st.floats(0.02, 1.0),
    eps=st.sampled_from([0.0, 0.05]),
    dtype=st.sampled_from(["float32", "bfloat16"]),
    capmode=st.sampled_from(["auto", "exact", "slack", "overflow"]),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_activation_skip_bit_identity(M, K, N, tm, tn, bd, dy, eps,
                                               dtype, capmode, seed):
    """Invariant (ISSUE 5): for ANY ragged geometry, activation block
    pattern, dtype, eps, and capacity within budget, the compiled capacity
    block-skip route is bit-identical to its unfused reference; a
    capacity below the need flips the in-program overflow fallback to the
    plain dense GEMM (bit-identical to that route instead)."""
    from repro.core import DynasparseEngine
    from repro.core import dispatch as dispatch_mod
    from repro.kernels import ops as kops
    from spmm_reference import activation_reference

    if dtype == "bfloat16":
        import ml_dtypes
        np_dtype = ml_dtypes.bfloat16
    else:
        np_dtype = np.float32
    rng = np.random.default_rng(seed)
    B = 8
    nrb, ncb = -(-M // B), -(-K // B)
    mask = (rng.uniform(size=(nrb, ncb)) < bd).astype(np.float32)
    xd = ((rng.normal(size=(nrb * B, ncb * B))
           * np.kron(mask, np.ones((B, B))))[:M, :K]).astype(np_dtype)
    yd = (rng.normal(size=(K, N)) *
          (rng.uniform(size=(K, N)) < dy)).astype(np.float32)
    eng = DynasparseEngine(tile_m=tm, tile_n=tn, literal=True,
                           interpret=True, eps=eps)
    plan = eng.plan(xd, jnp.asarray(yd))
    if not plan.stq:
        return                                    # dense wins: no route
    need = dispatch_mod.activation_capacity(xd, plan.part, B, eps=eps,
                                            slack=1.0)
    cap = {"auto": None, "exact": need, "slack": need + 3,
           "overflow": max(1, need - 1)}[capmode]
    ad = eng.activation_dispatch_for(plan, xd, capacity=cap)
    assert ad is not None
    z_a, diag = dispatch_mod.execute_activation(ad, xd, yd, interpret=True)
    z_a = np.asarray(z_a)
    if capmode == "overflow" and need > 1:
        assert bool(diag["overflow"])
        z_d = kops.gemm(jnp.asarray(xd), jnp.asarray(yd), interpret=True,
                        out_dtype=jnp.float32)
        np.testing.assert_array_equal(z_a, np.asarray(z_d))
        return
    assert not bool(diag["overflow"])
    np.testing.assert_array_equal(
        z_a, activation_reference(plan, xd, yd, eps=eps))
    if eps == 0.0:
        np.testing.assert_allclose(
            z_a, np.asarray(xd, np.float32) @ yd, rtol=2e-2, atol=2e-2)


def _naive_attention(q, k, v, causal=False):
    B, Lq, Hq, Dh = q.shape
    _, Lk, Hkv, _ = k.shape
    G = Hq // Hkv
    qf = q.astype(np.float32).reshape(B, Lq, Hkv, G, Dh)
    s = np.einsum("bqhgd,bkhd->bhgqk", qf, np.asarray(k, np.float32))
    s /= np.sqrt(Dh)
    if causal:
        mask = np.arange(Lk)[None, :] <= np.arange(Lq)[:, None]
        s = np.where(mask, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    out = np.einsum("bhgqk,bkhd->bqhgd", p, np.asarray(v, np.float32))
    return out.reshape(B, Lq, Hq, Dh)


@settings(max_examples=10, deadline=None)
@given(lq=st.integers(1, 33), lk=st.integers(1, 33), seed=st.integers(0, 999))
def test_property_flash_attention_ragged(lq, lk, seed):
    """Invariant: flash == naive for arbitrary (non-chunk-aligned) lengths,
    cross-attention style."""
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(1, lq, 2, 8)).astype(np.float32)
    k = rng.normal(size=(1, lk, 2, 8)).astype(np.float32)
    v = rng.normal(size=(1, lk, 2, 8)).astype(np.float32)
    got = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=False, q_chunk=8, kv_chunk=8)
    want = _naive_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), want, rtol=5e-3, atol=5e-3)
