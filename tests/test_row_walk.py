"""Whole-row walks of the compiled sparse sections.

A sparse section (SpDMM, or the SpMM stripe walk) whose tasks cover every
column tile of each row stripe they touch is lowered as one grid step per
stored block over the whole ``nct * SN``-wide padded output row, instead of
one per (stored block, column tile).  These tests pin when the walk is
chosen, that each walk is bit for bit its exact unfused reference and the
two agree within float32 rounding (the per-column sums are the same; on the
CPU a dot's rounding may follow its width), that the wide walk keeps the
in-place canvas's other tiles and survives entry lists split across
launches, and that the engine's dispatch makes the same choice.
"""
import dataclasses
import types

import numpy as np
import jax.numpy as jnp
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.core import DynasparseEngine, SparseCOO
from repro.core import dispatch as dispatch_mod
from repro.core.partition import make_tasks
from repro.kernels import ops
from repro.kernels.formats import pack_blockcsr
from spmm_reference import (eps_masked, pertask_reference, section_reference,
                            stripe_walk_reference)

# The TPU interpreter starts every VMEM buffer as NaN, as the chip leaves it
# uninitialized: a wide block the walk failed to cover or resume reads NaN.
TPU_INTERPRET = pltpu.InterpretParams(uninitialized_memory="nan")

B = 8


def _operands(seed, M=48, K=40, N=44, density=0.3):
    rng = np.random.default_rng(seed)
    xd = (rng.normal(size=(M, K)) *
          (rng.uniform(size=(M, K)) < density)).astype(np.float32)
    yd = rng.normal(size=(K, N)).astype(np.float32)
    yd[8:16, 16:24] = 0.1                # a sub-eps block for eps = 0.2
    return xd, yd


def _assigned(part, route):
    """``part``'s tasks with ``route(task)`` in ("GEMM", "SpDMM", "SpMM");
    returns (stq, dtq)."""
    stq, dtq = [], []
    for t in part.tasks:
        prim = route(t)
        t = dataclasses.replace(t, primitive=prim,
                                queue="DTQ" if prim == "GEMM" else "STQ")
        (dtq if prim == "GEMM" else stq).append(t)
    return stq, dtq


def _stripes(part, xd, eps):
    return {i: pack_blockcsr(xd[i * part.tile_m:(i + 1) * part.tile_m], B,
                             eps=eps)
            for i in range(part.n_row_tiles)}


def _stripe_walk_of(d, part, stq, stripes):
    """``d`` with both sparse sections lowered one column stripe at a time,
    as they were before whole-row walks."""
    R = d.geom.SM // B
    arrays = dict(d.arrays)
    for prefix, tasks in (("sp", [t for t in stq if t.primitive != "SpMM"]),
                          ("mm", [t for t in stq if t.primitive == "SpMM"])):
        if tasks:
            offsets, _ = dispatch_mod._stripe_pool(tasks, stripes)
            for name, v in zip(dispatch_mod.ENTRY_FIELDS,
                               dispatch_mod.spdmm_entry_arrays(
                                   tasks, stripes, offsets, R)):
                arrays[f"{prefix}_{name}"] = jnp.asarray(v)
    geom = dataclasses.replace(d.geom, sp_rows=False, mm_rows=False)
    return geom, arrays


def _run(geom, arrays, xd, yd, interpret=TPU_INTERPRET):
    return np.asarray(dispatch_mod.apply_dispatch(
        geom, arrays, jnp.asarray(xd), jnp.asarray(yd), interpret=interpret))


def test_walk_choice_follows_the_task_assignment_and_width():
    part = make_tasks("k", 48, 40, 44, np.ones(3), np.ones(3), 16, 16)
    full = part.tasks
    assert dispatch_mod.walks_whole_rows(full, 3, 16, B)
    # a row stripe missing one column tile keeps the per-stripe walk
    assert not dispatch_mod.walks_whole_rows(full[1:], 3, 16, B)
    # but a section that leaves whole row stripes out still walks rows
    assert dispatch_mod.walks_whole_rows(
        [t for t in full if t.i != 1], 3, 16, B)
    # no tasks, or one column tile: nothing to walk or merge
    assert not dispatch_mod.walks_whole_rows([], 3, 16, B)
    assert not dispatch_mod.walks_whole_rows(
        [t for t in full if t.j == 0], 1, 16, B)
    # rows wider than the VMEM cap walk one stripe at a time
    two = make_tasks("k", 16, 8, 32, np.ones(1), np.ones(2), 16, 16).tasks
    cap_cols = ops.MAX_ROW_BLOCK_BYTES // (4 * B)
    assert cap_cols == 16_384
    assert dispatch_mod.walks_whole_rows(two, 2, cap_cols // 2, B)
    assert not dispatch_mod.walks_whole_rows(two, 2, cap_cols // 2 + 8, B)


@pytest.mark.parametrize("prim,eps", [("SpDMM", 0.0), ("SpMM", 0.2)])
def test_row_walk_matches_stripe_walk_and_references(prim, eps):
    """Every task on one primitive over 3 x 3 tiles whose last column tile
    is 12 of 16 columns wide: the section walks 48-wide rows.  Each walk is
    bit for bit its unfused reference (on the eps-masked Y for SpMM), the
    two walks agree within float32 rounding, and both match the product."""
    xd, yd = _operands(seed=3)
    M, K = xd.shape
    N = yd.shape[1]
    part = make_tasks("k", M, K, N, np.ones(3), np.ones(3), 16, 16)
    assert part.col_extent(2) == 12
    stq, dtq = _assigned(part, lambda t: prim)
    stripes = _stripes(part, xd, eps)
    d = dispatch_mod.build_dispatch(part, stq, dtq, stripes, block=B, eps=eps)
    assert (d.geom.sp_rows, d.geom.mm_rows) == (prim == "SpDMM",
                                                prim == "SpMM")
    assert d.row_walk_sections == 1
    assert d.sparse_steps == sum(s.nnzb for s in stripes.values())
    z_rows = _run(d.geom, d.arrays, xd, yd)
    geom_s, arrays_s = _stripe_walk_of(d, part, stq, stripes)
    z_cols = _run(geom_s, arrays_s, xd, yd)
    assert int(arrays_s["sp_a_ids" if prim == "SpDMM" else "mm_a_ids"]
               .shape[0]) == 3 * d.sparse_steps

    y_ref = eps_masked(yd, eps) if prim == "SpMM" else yd
    for z, whole in ((z_rows, True), (z_cols, False)):
        ref = section_reference(part, stq, xd, y_ref, whole_rows=whole,
                                eps=eps)[:M, :N]
        np.testing.assert_array_equal(z, ref)
        np.testing.assert_allclose(
            z, eps_masked(xd, eps).astype(np.float64) @ y_ref,
            rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(z_rows, z_cols, rtol=1e-5, atol=1e-5)


def _three_route_plan(route_name):
    """48 x 40 x 44 in 3 x 3 tiles.  ``by_stripe``: stripe 0 on GEMM,
    stripe 1 on SpDMM, stripe 2 on SpMM — both sparse sections cover their
    stripes and walk whole rows beside the GEMM tiles of the canvas.
    ``in_stripe``: the routes alternate inside each stripe, so neither
    section covers a stripe and both keep the per-stripe walk."""
    xd, yd = _operands(seed=5)
    M, K = xd.shape
    part = make_tasks("k", M, K, yd.shape[1], np.ones(3), np.ones(3), 16, 16)
    prims = ("GEMM", "SpDMM", "SpMM")
    if route_name == "by_stripe":
        stq, dtq = _assigned(part, lambda t: prims[t.i])
    else:
        stq, dtq = _assigned(part, lambda t: prims[(t.i + t.j) % 3])
    return part, stq, dtq, xd, yd


@pytest.mark.parametrize("route_name", ["by_stripe", "in_stripe"])
def test_in_place_canvas_keeps_gemm_tiles(route_name):
    """The wide blocks write only the rows of the stripes their section
    covers: the GEMM tiles of other stripes survive in the aliased canvas.
    Compiled equals its eager reference bitwise in either layout."""
    part, stq, dtq, xd, yd = _three_route_plan(route_name)
    walks = route_name == "by_stripe"
    stripes = _stripes(part, xd, 0.0)
    d = dispatch_mod.build_dispatch(part, stq, dtq, stripes, block=B)
    assert d.geom.has_gemm and d.geom.has_spdmm and d.geom.has_spmm
    assert (d.geom.sp_rows, d.geom.mm_rows) == (walks, walks)
    assert d.row_walk_sections == 2 * walks
    z = _run(d.geom, d.arrays, xd, yd)
    plan = types.SimpleNamespace(part=part, stq=stq, dtq=dtq)
    np.testing.assert_array_equal(z, stripe_walk_reference(plan, xd, yd))
    np.testing.assert_allclose(z, xd @ yd, rtol=1e-4, atol=1e-4)
    if walks:
        gemm_rows = pertask_reference(part, [], dtq, xd, yd)[:16]
        np.testing.assert_array_equal(z[:16], gemm_rows)


@pytest.mark.parametrize("max_entries", [1, 3, 7])
def test_row_walk_split_across_launches_bitwise(max_entries, monkeypatch):
    """Entry lists split across launches resume their wide partial blocks
    from the canvas: the split run gives the single launch's bits, beside
    the GEMM tiles, under an interpreter that starts VMEM as NaN."""
    part, stq, dtq, xd, yd = _three_route_plan("by_stripe")
    stripes = _stripes(part, xd, 0.0)
    d = dispatch_mod.build_dispatch(part, stq, dtq, stripes, block=B)
    one = _run(d.geom, d.arrays, xd, yd)
    monkeypatch.setattr(ops, "MAX_ENTRIES_PER_LAUNCH", max_entries)
    launches0 = ops.pallas_call_count()
    split = _run(d.geom, d.arrays, xd, yd)
    n_sp = int(d.arrays["sp_a_ids"].shape[0])
    n_mm = int(d.arrays["mm_a_ids"].shape[0])
    assert ops.pallas_call_count() - launches0 == (
        1 + -(-n_sp // max_entries) + -(-n_mm // max_entries))
    np.testing.assert_array_equal(split, one)
    assert np.isfinite(split).all()


def _coo_of(xd):
    r, c = np.nonzero(xd)
    return SparseCOO(xd.shape, jnp.asarray(r.astype(np.int32)),
                     jnp.asarray(c.astype(np.int32)),
                     jnp.asarray(xd[r, c]), tag="adjacency")


@pytest.mark.parametrize("tn", [8, 16])
def test_compiled_row_walk_bit_identical_to_eager(tn):
    """An engine plan with every task on SpDMM: the engine's dispatch walks
    whole rows, bit for bit the whole-row walk's unfused reference."""
    xd, yd = _operands(seed=9, M=56, K=48, N=40)
    x = _coo_of(xd)
    eng = DynasparseEngine(tile_m=16, tile_n=tn, literal=True)
    plan = eng.plan(x, jnp.asarray(yd))
    plan = dataclasses.replace(plan, dtq=[], stq=[
        dataclasses.replace(t, primitive="SpDMM", queue="STQ")
        for t in plan.stq + plan.dtq])
    assert plan.part.n_col_tiles == -(-40 // tn)
    z_c = np.asarray(eng.execute(plan, x, jnp.asarray(yd)))
    d = eng.dispatch_for(plan, x)
    assert d.geom.sp_rows and d.row_walk_sections == 1
    z_r = section_reference(plan.part, plan.stq, xd, yd, whole_rows=True)
    np.testing.assert_array_equal(z_c, z_r[:xd.shape[0], :yd.shape[1]])
    np.testing.assert_allclose(z_c, xd @ yd, rtol=1e-4, atol=1e-4)
