"""Compile rehearsals for TPU v5e, run without a chip.

The TPU compiler is installed with jax and compiles for a chip that is
described, not attached.  It refuses what the Pallas interpreter lets pass:
blocks that break the (8, 128) tiling rule, more scalar-prefetch memory
(SMEM) than a launch may use, a Mosaic kernel the partitioner would have to
split.  Each test compiles one kernel of the served GCN path at the
geometry ``chip_smoke.py`` produces — GCN on CO at its Table IV size,
stacked to width 128 by ``max_batch=8`` — plus the whole-model program.
Nothing runs: these say nothing about results or times.

The topology is described inside a module fixture: only the worker that is
given this file loads the TPU library, and it skips where the library
cannot describe a chip.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

# GCN on CO, 2,708 vertices, 1,433 features, hidden 16, 7 classes, stacked
# over 8 requests; row stripes of the engine's default geometry
B = 8                       # engine block
M_ADJ, K_FEAT = 2708, 1433
M_ACT = 8 * M_ADJ           # activations unstacked to rows: 21,664
SM_ADJ, SM_ACT = 384, 2816  # row-stripe slots (8 stripes each)
ADJ_BLOCKS = 5499           # stored 8x8 blocks of the normalized adjacency
ACT_SLOTS = 407_012         # capacity slots of the features' block-skip pack


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, f, *shapes):
    """Compile ``f`` for the described chip, the persistent compilation
    cache off (an entry written without a chip cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        compiled = jax.jit(f).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()
    return compiled


def _compile_kernel(one_chip, f, *shapes):
    """:func:`_compile`, asserting the program holds compiled Mosaic
    kernels (interpret mode would lower to plain XLA instead)."""
    compiled = _compile(one_chip, f, *shapes)
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


F32, I32 = jnp.float32, jnp.int32


@pytest.mark.parametrize("entries,width,m_pad,k_pad", [
    (ADJ_BLOCKS, 128, 8 * SM_ADJ, 2712),     # l1-agg: adjacency x (N, 128)
    (ADJ_BLOCKS, 56, 8 * SM_ADJ, 2712),      # l2-agg: adjacency x (N, 56)
    (ACT_SLOTS, 16, 8 * SM_ACT, 1440),       # l1-update: features x W1
])
def test_spdmm_fused_compiles(one_chip, entries, width, m_pad, k_pad):
    """Includes a list longer than one launch's SMEM holds (l1-update)."""
    def f(pool, y, a, r, o, c, first, z):
        return ops.spdmm_fused(pool, y, a, r, o, c, first, block_size=B,
                               bn=width, m_pad=m_pad, interpret=False, z=z)
    ids = [((entries,), I32)] * 5
    _compile_kernel(one_chip, f, ((entries, B, B), F32),
                    ((k_pad, width), F32), *ids, ((m_pad, width), F32))


def test_spdmm_fused_narrow_stripes_compile(one_chip):
    """Column stripes narrower than 128 lanes with several stripes (a
    ``tile_n=8`` geometry) — refused before the stripe-major layout."""
    def f(pool, y, a, r, o, c, first, z):
        return ops.spdmm_fused(pool, y, a, r, o, c, first, block_size=B,
                               bn=8, m_pad=8 * SM_ADJ, interpret=False, z=z)
    ids = [((ADJ_BLOCKS,), I32)] * 5
    _compile_kernel(one_chip, f, ((ADJ_BLOCKS, B, B), F32),
                    ((2712, 64), F32), *ids, ((8 * SM_ADJ, 64), F32))


def test_spmm_fused_compiles(one_chip):
    """l2-agg (adjacency x a 56-wide Y) had the Analyzer chosen SpMM: every
    stored block paired with the 7 Y blocks of its block-row."""
    n = ADJ_BLOCKS * 7

    def f(a_pool, y_pool, a, yi, o, c, first, z):
        return ops.spmm_fused(a_pool, y_pool, a, yi, o, c, first,
                              block_size=B, m_pad=8 * SM_ADJ, n_pad=56,
                              interpret=False, z=z)
    ids = [((n,), I32)] * 5
    _compile_kernel(one_chip, f, ((ADJ_BLOCKS + 1, B, B), F32),
                    ((339 * 7 + 1, B, B), F32), *ids, ((8 * SM_ADJ, 56), F32))


def test_spmm_stripe_walk_compiles(one_chip):
    """GIN's l1-agg as the compiled SpMM section lowers it: the stripe walk
    over 8 column stripes 1,536 wide (eight stacked requests of 1,433
    features), one step per stored block and stripe, in two launches under
    its own name."""
    n = ADJ_BLOCKS * 8
    width = 8 * 1536

    def f(pool, y, a, r, o, c, first, z):
        return ops.spdmm_fused(pool, y, a, r, o, c, first, block_size=B,
                               bn=1536, m_pad=8 * SM_ADJ, interpret=False,
                               z=z, name="spmm_stripe")
    ids = [((n,), I32)] * 5
    compiled = _compile_kernel(one_chip, f, ((ADJ_BLOCKS, B, B), F32),
                               ((2712, width), F32), *ids,
                               ((8 * SM_ADJ, width), F32))
    assert compiled.as_text().count("spmm_stripe") >= 2


@pytest.mark.parametrize("entries,width,m_pad,k_pad,name,launches", [
    # GIN's l1-agg on CO: 5,499 steps over 12,288-wide rows, the widest a
    # served kernel walks, at 384 KiB a block
    (ADJ_BLOCKS, 8 * 1536, 8 * SM_ADJ, 2712, "spmm_stripe", 1),
    # SAGE's l1-agg on FL: 900,699 steps over 1,024-wide rows
    (900_699, 8 * 128, 8 * 11264, 89256, "spdmm_fused", 28),
])
def test_row_walk_compiles(one_chip, entries, width, m_pad, k_pad, name,
                           launches):
    """A sparse section walking whole rows: one stripe as wide as the
    padded row, so the Y, output and canvas blocks span the arrays' whole
    minor dimension; split across launches under the section's name."""
    def f(pool, y, a, r, o, c, first, z):
        return ops.spdmm_fused(pool, y, a, r, o, c, first, block_size=B,
                               bn=width, m_pad=m_pad, interpret=False, z=z,
                               name=name)
    ids = [((entries,), I32)] * 5
    compiled = _compile_kernel(one_chip, f, ((entries, B, B), F32),
                               ((k_pad, width), F32), *ids,
                               ((m_pad, width), F32))
    assert compiled.as_text().count("tpu_custom_call") == launches


@pytest.mark.parametrize("tiles,sm,k,n", [
    (8, SM_ACT, K_FEAT, 16),   # l1-update routed to the dense queue
    (4, SM_ACT, 16, 8),        # l2-update's dense half
    (8, SM_ADJ, M_ADJ, 128),   # l1-agg stripes routed to the dense queue
])
def test_gemm_batch_scatter_compiles(one_chip, tiles, sm, k, n):
    def f(x, y, rows, cols, z):
        return ops.gemm_batch_scatter(x, y, rows, cols, z, interpret=False)
    _compile_kernel(one_chip, f, ((tiles, sm, k), F32),
                    ((tiles, k, n), F32), ((tiles,), I32), ((tiles,), I32),
                    ((8 * sm, n), F32))


@pytest.mark.parametrize("k,n", [(K_FEAT, 16), (16, 7)])
def test_gemm_compiles(one_chip, k, n):
    """The activation kernels' dense route (and overflow fallback)."""
    def f(x, y):
        return ops.gemm(x, y, interpret=False, out_dtype=jnp.float32)
    _compile_kernel(one_chip, f, ((M_ACT, k), F32), ((k, n), F32))


def _pack(x):
    return ops.pack_activation_stripes(
        x, block=B, n_stripes=8, slot_rows=SM_ACT // B,
        n_block_cols=-(-K_FEAT // B), capacity=np.full(8, ACT_SLOTS // 8))


def test_pack_activation_stripes_compiles(one_chip):
    """The device-side block-skip packer of the features (plain XLA)."""
    _compile(one_chip, _pack, ((M_ACT, K_FEAT), F32))


def test_whole_model_program_compiles(one_chip, monkeypatch):
    """The served path's whole-model program (``CompiledModel.run``) for
    GCN on CO at stacked width 128, compiled for one chip.  The warmup pass
    that records its kernels takes each kernel's route but computes it with
    the plain jnp executor here, so that only the geometry is taken from
    the CPU; the kernels are traced for the TPU (interpret mode off)."""
    from repro.core import DynasparseEngine
    from repro.core import primitives as prim
    from repro.core.perfmodel import TPUV5E
    from repro.data.graphs import load_graph
    from repro.models import gnn
    from repro.serving.engine import stacked_transport

    g = load_graph("CO")
    params = gnn.init_params("GCN", K_FEAT, g.stats.hidden, g.stats.classes)
    engine = DynasparseEngine(TPUV5E, literal=True, calibration="off")
    def execute(plan, x, y):
        engine.last_route = engine.route(plan, x)
        if isinstance(x, prim.SparseCOO):
            return prim.spdmm_exec(x, y)
        return prim.gemm_exec(jnp.asarray(x), jnp.asarray(y))
    monkeypatch.setattr(engine, "execute", execute)
    h = jnp.concatenate([g.features_dense] * 8, axis=1)
    _, cm = gnn.compile_model("GCN", engine, g.adj, h, params,
                              transport=stacked_transport)
    assert cm is not None and cm.n_sparse == 2 and cm.n_act == 2

    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    leaves, tree = jax.tree.flatten(cm.payload)
    shapes = [(v.shape, v.dtype) for v in leaves] + [(h.shape, h.dtype)]

    def program(*args):
        return cm.run(jax.tree.unflatten(tree, args[:-1]), args[-1])
    compiled = _compile_kernel(one_chip, program, *shapes)
    assert compiled.as_text().count("tpu_custom_call") >= 4
