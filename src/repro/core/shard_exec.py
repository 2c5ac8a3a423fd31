"""Sharded compiled dispatch — one per-shard program under ``shard_map``.

A device-placed plan (``KernelPlan.placement`` from
:func:`repro.core.analyzer.analyze_sharded`) lowers here into a
:class:`ShardedDispatch`: the same descriptor arrays a
:class:`~repro.core.dispatch.CompiledDispatch` carries, but banded by device
(leading device axis, contiguous LOCAL row numbering inside each band) and
executed by ONE ``shard_map``-wrapped :func:`~repro.core.dispatch.apply_prepared`
body on a 1-D ``("data",)`` mesh.  Mesh size 1 is the degenerate case of the
same code path — there is no single-device fork — and the result is
bit-identical to the unsharded executor (see below).

Uniform shard geometry via a GHOST row-tile
-------------------------------------------
``shard_map`` needs every shard to run the identical program on
identically-shaped operands, but min-makespan bands are ragged (different
stripe counts per device; stripe counts need not divide the device count).
Each shard therefore gets ``nrt_local = max_band_tiles + 1`` row tiles: real
bands occupy a prefix, and the extra GHOST tile absorbs all descriptor
padding needed to equalize per-device entry counts:

- GEMM pads address output tile ``(nrt_local - 1, 0)`` — the gathered X slab
  for the ghost tile is all zeros, so the scatter overwrites the ghost tile
  with zeros;
- SpDMM / SpMM pads (both sections use the SpDMM entry format) reference an
  appended all-zero pool block with ``first = 0`` at the ghost tile's first
  block-row, so they ACCUMULATE ``0 · Y`` into an already-zero canvas block
  (the kernels' ``first == 1`` zero-init / ``first == 0`` accumulate
  semantics make this an exact bitwise no-op — the same sentinel-zero-block
  idiom ``kernels/spmm.py`` uses for its own padding triples).

Owned-operand sharding with halo exchange (``operand_sharding="halo"``)
-----------------------------------------------------------------------
By default the dense operand Y no longer enters the program replicated.
Lowering runs a per-band COLUMN-SUPPORT analysis over the descriptors it
just built (SpDMM and SpMM entries name their Y block-rows directly; GEMM
bands read everything → replicated fallback), emits one
:class:`repro.core.halo.ColumnSupport` per device, and compiles a static
ring-exchange schedule (:func:`repro.core.halo.build_exchange`).  Y is
split by block-row OWNERSHIP outside the program (each shard's
``in_spec P("data")`` slab holds only its owned rows), the ``shard_map``
body first runs ``nd - 1`` ``ppermute`` rounds copying halo
blocks into a local ``(L + 1)`` slot owned+halo buffer, and the SpDMM/SpMM
descriptors — rewritten at lowering time from global block-rows to local
buffer slots — feed the very same fused kernels.  Per-device dense-operand
memory drops from ``O(ncb)`` block-rows to ``O(max_own + max_support)``;
a fully block-diagonal graph has empty halos and emits ZERO collectives.
``operand_sharding="replicate"`` keeps the PR 8 layout as the bitwise
correctness oracle.

Bit-identity with the unsharded executor holds because every REAL output
block receives exactly the contribution sequence it receives globally: the
per-band entry sort (local ``out_row`` = global ``out_row`` − band offset)
preserves the global per-block ordering, the halo exchange is pure data
movement of the rows ``_stripe_padded_y`` lays out globally (descriptor
entry ORDER never changes, only Y indices are remapped to local slots), and
float accumulation order per block is unchanged.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import dispatch as _dispatch
from repro.core import halo as _halo

OPERAND_SHARDINGS = ("halo", "replicate")


@dataclasses.dataclass
class ShardedDispatch:
    """Device-banded instruction stream of one placed kernel.

    ``geom`` is the per-shard LOCAL geometry (uniform across devices:
    ``nrt = max_band_tiles + 1`` with the ghost tile, ``M = m_pad``).
    ``arrays`` mirrors :class:`~repro.core.dispatch.CompiledDispatch.arrays`
    with a leading device axis — in halo mode that includes the exchange
    schedule index arrays (``hx_*``), so snapshot restore re-uploads them
    with everything else.  ``band_rows[d]`` is the count of logical output
    rows device ``d`` owns (the final assembly concatenates
    ``z[d, :band_rows[d]]``).  ``halo`` is the static
    :class:`~repro.core.halo.HaloGeometry` (``None`` → replicated operand),
    ``supports`` the per-device column supports, and ``operand_bytes`` the
    analytic per-device dense-operand memory accounting
    (``dispatch_stats()`` aggregates it).
    """
    geom: _dispatch.DispatchGeometry
    n_devices: int
    band_starts: tuple[int, ...]
    band_rows: tuple[int, ...]
    M: int                             # global logical row count
    arrays: dict[str, jax.Array]
    fingerprint: str
    supports: tuple = ()
    halo: object = None                # _halo.HaloGeometry | None
    operand_sharding: str = "replicate"
    operand_bytes: dict = dataclasses.field(default_factory=dict)

    @property
    def needs_x(self) -> bool:
        return self.geom.has_gemm

    @property
    def sparse_steps(self) -> int:
        """Grid steps of both sparse sections on each device (every shard
        walks the same padded entry count)."""
        return sum(int(self.arrays[k].shape[-1])
                   for k in ("sp_a_ids", "mm_a_ids") if k in self.arrays)

    @property
    def row_walk_sections(self) -> int:
        """Sparse sections lowered as whole-row walks."""
        return int(self.geom.sp_rows) + int(self.geom.mm_rows)


def _pool_dtype(stripes):
    for s in stripes.values():
        return np.asarray(s.blocks).dtype
    return np.dtype(np.float32)


def _band_tasks(tasks, placement, d):
    lo, hi = placement.band_starts[d], placement.band_starts[d + 1]
    return [dataclasses.replace(t, i=t.i - lo) for t in tasks if lo <= t.i < hi]


def _column_supports(per_gemm, per_spdmm, per_spmm, own_starts, ncb):
    """Per-device :class:`~repro.core.halo.ColumnSupport` from the lowered
    descriptor arrays: SpDMM and SpMM entries carry Y block-rows in
    ``y_rows``, and a band with real GEMM tasks reads the whole operand
    (replicated fallback)."""
    nd = len(own_starts) - 1
    supports = []
    for d in range(nd):
        full = len(per_gemm[d]) > 0
        if full:
            read = set(range(ncb))
        else:
            read = set()
            for _, e in (per_spdmm[d], per_spmm[d]):
                if e is not None:
                    read.update(int(g) for g in np.unique(e[1]))
        own = range(own_starts[d], own_starts[d + 1])
        supports.append(_halo.ColumnSupport(
            own_start=own_starts[d], own_stop=own_starts[d + 1],
            halo=tuple(sorted(read - set(own))), full=full))
    return tuple(supports)


def _localize_entries(supports, per_spdmm, per_spmm, ncb):
    """Rewrite Y indices from GLOBAL block-rows to LOCAL owned+halo buffer
    slots, per device.  Entry order (hence accumulation order) untouched."""
    sp_out, mm_out = [], []
    for cs, sp, mm in zip(supports, per_spdmm, per_spmm):
        lut = np.zeros(ncb, np.int64)
        for slot, g in enumerate(cs.local_blocks()):
            lut[g] = slot
        for (pool, e), out in ((sp, sp_out), (mm, mm_out)):
            out.append((pool, None if e is None
                        else (e[0], lut[e[1]], *e[2:])))
    return sp_out, mm_out


def build_sharded_dispatch(part, stq, dtq, stripes, placement,
                           *, block: int, eps: float = 0.0,
                           fingerprint: str = "",
                           operand_sharding: str = "halo",
                           faults: object = None,
                           mesh=None) -> ShardedDispatch:
    """Lower a device-placed plan into a :class:`ShardedDispatch`.

    Same O(nnz blocks) vectorized-numpy cost as
    :func:`~repro.core.dispatch.build_dispatch`, paid once per (structure,
    assignment, mesh geometry, operand-sharding mode), and the same
    ``ValueError`` for a canvas the in-place index maps cannot take.
    With ``mesh``, every per-device array is uploaded split along its
    leading device axis (``P("data")``), so each device holds only its own
    band's descriptors and pool and the program never reshards them.
    """
    if mesh is None:
        put = jnp.asarray
    else:
        data = NamedSharding(mesh, P("data"))

        def put(v):
            return jax.device_put(np.asarray(v), data)
    if operand_sharding not in OPERAND_SHARDINGS:
        raise ValueError(f"operand_sharding must be one of "
                         f"{OPERAND_SHARDINGS}, got {operand_sharding!r}")
    if faults is not None:
        faults.probe("shard_lower", detail=f"shard:{part.name}")
    SM, SN = _dispatch.canvas_slots(part, block)
    B = block
    R = SM // B
    nd = placement.n_devices
    bs = placement.band_starts
    max_band = max(placement.band_sizes()) if nd else 0
    nrt_l = max_band + 1                       # +1 ghost tile for padding
    ghost_row = (nrt_l - 1) * R                # first block-row of the ghost

    band_rows = tuple(
        sum(part.row_extent(i) for i in placement.stripes_of(d))
        for d in range(nd))

    sp_all = [t for t in stq if t.primitive != "SpMM"]
    mm_all = [t for t in stq if t.primitive == "SpMM"]
    # one geometry serves every shard: decided on the whole section, the
    # whole-row walk holds in every band (each stripe lies in one band)
    sp_rows = _dispatch.walks_whole_rows(sp_all, part.n_col_tiles, SN, B)
    mm_rows = _dispatch.walks_whole_rows(mm_all, part.n_col_tiles, SN, B)

    per_gemm, per_spdmm, per_spmm = [], [], []
    for d in range(nd):
        lo = bs[d]
        local_stripes = {i - lo: stripes[i] for i in placement.stripes_of(d)
                         if i in stripes}
        per_gemm.append(_band_tasks(dtq, placement, d))
        for tasks, rows, per in ((sp_all, sp_rows, per_spdmm),
                                 (mm_all, mm_rows, per_spmm)):
            band = _band_tasks(tasks, placement, d)
            if band:
                offsets, pool = _dispatch._stripe_pool(band, local_stripes)
                per.append((np.asarray(pool), _dispatch.spdmm_entry_arrays(
                    band, local_stripes, offsets, R, whole_rows=rows)))
            else:
                per.append((np.zeros((0, B, B), _pool_dtype(stripes)), None))

    n_gemm = max((len(g) for g in per_gemm), default=0)

    ncb = -(-part.K // B)
    supports: tuple = ()
    hg = None
    hx_arrays: dict[str, np.ndarray] = {}
    if operand_sharding == "halo":
        own_starts = _halo.ownership_starts(part.M, part.K, part.tile_m,
                                            bs, B)
        supports = _column_supports(per_gemm, per_spdmm, per_spmm,
                                    own_starts, ncb)
        per_spdmm, per_spmm = _localize_entries(supports, per_spdmm,
                                                per_spmm, ncb)
        hg, own_dst, hx_src, hx_dst, gather = _halo.build_exchange(
            supports, own_starts, gather=n_gemm > 0)
        hx_arrays = {"hx_own_dst": own_dst, "hx_src": hx_src,
                     "hx_dst": hx_dst}
        if gather is not None:
            hx_arrays["hx_gather"] = gather

    n_sp = max((0 if e is None else len(e[0]) for _, e in per_spdmm),
               default=0)
    n_mm = max((0 if e is None else len(e[0]) for _, e in per_spmm),
               default=0)

    geom = _dispatch.DispatchGeometry(
        M=nrt_l * SM, K=part.K, N=part.N, tm=part.tile_m, tn=part.tile_n,
        SM=SM, SN=SN, B=B, nrt=nrt_l, nct=part.n_col_tiles,
        has_gemm=n_gemm > 0, has_spdmm=n_sp > 0, has_spmm=n_mm > 0,
        eps=eps, sp_rows=sp_rows, mm_rows=mm_rows)

    arrays: dict[str, jax.Array] = {k: put(v) for k, v in hx_arrays.items()}

    if n_gemm:
        rows = np.full((nd, n_gemm), nrt_l - 1, dtype=np.int32)
        cols = np.zeros((nd, n_gemm), dtype=np.int32)
        for d, g in enumerate(per_gemm):
            rows[d, :len(g)] = [t.i for t in g]
            cols[d, :len(g)] = [t.j for t in g]
        arrays["gemm_rows"] = put(rows)
        arrays["gemm_cols"] = put(cols)

    def _stack_section(per_dev, n_entries, names, pad_cols):
        """Pad each device's (pool, entry-arrays) to common shapes and
        stack.  ``pad_cols[k]`` gives the pad value per entry column as a
        function of the padded pool length."""
        pool_len = max(len(p) for p, _ in per_dev) + 1   # +1 zero sentinel
        pools, columns = [], [[] for _ in names]
        for pool, entries in per_dev:
            pools.append(np.concatenate(
                [pool, np.zeros((pool_len - len(pool),) + pool.shape[1:],
                                pool.dtype)], axis=0))
            cols = (entries if entries is not None
                    else tuple(np.zeros(0, np.int32) for _ in names))
            pad_n = n_entries - len(cols[0])
            for k, c in enumerate(cols):
                columns[k].append(np.concatenate(
                    [c, np.full(pad_n, pad_cols[k](pool_len),
                                dtype=np.int32)]))
        out = {"pool": put(np.stack(pools))}
        for k, name in enumerate(names):
            out[name] = put(np.stack(columns[k]).astype(np.int32))
        return out

    for prefix, per_dev, n_entries in (("sp", per_spdmm, n_sp),
                                       ("mm", per_spmm, n_mm)):
        if not n_entries:
            continue
        sec = _stack_section(
            per_dev, n_entries, _dispatch.ENTRY_FIELDS,
            # pads: zero-sentinel A block × Y row 0 → ghost block, first=0
            # (in halo mode Y row 0 is local slot 0 — any resident block
            # works: a zero A block accumulates an exact bitwise no-op)
            (lambda pl: pl - 1, lambda pl: 0, lambda pl: ghost_row,
             lambda pl: 0, lambda pl: 0))
        arrays[f"{prefix}_pool"] = sec["pool"]
        for name in _dispatch.ENTRY_FIELDS:
            arrays[f"{prefix}_{name}"] = sec[name]

    width = part.n_col_tiles * SN
    if operand_sharding == "halo":
        op_bytes = _halo.operand_bytes(supports, hg, B, width)
    else:
        bb = B * width * 4
        op_bytes = {"mode": "replicate", "per_device": [
            {"owned_bytes": 0, "halo_bytes": 0, "fallback_bytes": ncb * bb,
             "full": True} for _ in range(nd)],
            "owned_bytes": 0, "halo_bytes": 0,
            "fallback_bytes": nd * ncb * bb,
            "halo_per_device_bytes": ncb * bb,
            "replicated_per_device_bytes": ncb * bb}

    return ShardedDispatch(geom=geom, n_devices=nd, band_starts=tuple(bs),
                           band_rows=band_rows, M=part.M, arrays=arrays,
                           fingerprint=fingerprint, supports=supports,
                           halo=hg, operand_sharding=operand_sharding,
                           operand_bytes=op_bytes)


def _x_slabs(geom, band_rows, x):
    """Per-band X slabs padded to the uniform shard height."""
    slabs, row0 = [], 0
    for r in band_rows:
        sl = jax.lax.slice_in_dim(x, row0, row0 + r, axis=0)
        slabs.append(jnp.pad(sl, ((0, geom.m_pad - r), (0, 0))))
        row0 += r
    return jnp.stack(slabs)


def _y_owned_slabs(geom, halo, y):
    """Owned block-row slabs of the stripe-padded operand, padded to
    ``max_own`` so every shard's ``in_spec P("data")`` slice is uniform."""
    B = geom.B
    W = geom.nct * geom.SN
    yb = _dispatch._stripe_padded_y(geom, y).reshape(geom.ncb, B, W)
    slabs = []
    for d in range(halo.n_devices):
        sl = yb[halo.own_starts[d]:halo.own_starts[d + 1]]
        slabs.append(jnp.pad(sl, ((0, halo.max_own - sl.shape[0]),
                                  (0, 0), (0, 0))))
    return jnp.stack(slabs)


def apply_sharded(geom, band_rows, arrays, x, y, *, mesh, interpret: bool,
                  halo=None):
    """Traceable sharded executor body: slab X per band (and, in halo mode,
    slab Y per OWNER) → ``shard_map`` the shared
    :func:`~repro.core.dispatch.apply_prepared` body → concatenate each
    band's logical rows.  Inlines into larger jitted programs
    (``models.gnn.compile_model``), exactly like the unsharded body."""
    nd = len(band_rows)
    y = jnp.asarray(y)

    if geom.has_gemm and x is None:
        raise ValueError("sharded dispatch: dense-queue tasks need the "
                         "densified x operand (got x=None)")

    if halo is None:
        # Replicated-operand oracle: Y enters every shard whole.
        def shard_body(local, x_l, y_rep):
            return _dispatch.apply_dispatch(geom, local, x_l, y_rep,
                                            interpret=interpret)
        y_in, y_spec = y, P()
    else:
        B, W = geom.B, geom.nct * geom.SN

        def shard_body(local, x_l, y_own):
            ybuf = _halo.exchange(local, y_own, halo)
            y_fl = ybuf.reshape((halo.L + 1) * B, W)
            y_pl = None
            if geom.has_gemm:
                y_pl = ybuf[local["hx_gather"]].reshape(
                    geom.ncb * B, geom.nct, geom.SN)[:geom.K]
            return _dispatch.apply_prepared(geom, local, x_l, y_fl, y_pl,
                                            interpret=interpret)
        y_in, y_spec = _y_owned_slabs(geom, halo, y), P("data")

    if geom.has_gemm:
        x_sh = _x_slabs(geom, band_rows, jnp.asarray(x))

        def body(arrs, xs, yy):
            local = {k: v[0] for k, v in arrs.items()}
            return shard_body(local, xs[0],
                              yy if halo is None else yy[0])[None]

        f = compat.shard_map(body, mesh=mesh,
                             in_specs=(P("data"), P("data"), y_spec),
                             out_specs=P("data"))
        zs = f(arrays, x_sh, y_in)
    else:
        def body(arrs, yy):
            local = {k: v[0] for k, v in arrs.items()}
            return shard_body(local, None,
                              yy if halo is None else yy[0])[None]

        f = compat.shard_map(body, mesh=mesh,
                             in_specs=(P("data"), y_spec),
                             out_specs=P("data"))
        zs = f(arrays, y_in)

    parts = [zs[d, :band_rows[d]] for d in range(nd) if band_rows[d]]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


@functools.partial(jax.jit,
                   static_argnames=("geom", "band_rows", "mesh", "interpret",
                                    "halo"))
def _run_sharded(geom, band_rows, arrays, x, y, *, mesh, interpret,
                 halo=None):
    return apply_sharded(geom, band_rows, arrays, x, y,
                         mesh=mesh, interpret=interpret, halo=halo)


def _shard_signature(sd, x, y, mesh, interpret):
    arr_sig = tuple(sorted((k, v.shape, str(v.dtype))
                           for k, v in sd.arrays.items()))
    x_sig = None if x is None else (tuple(x.shape), str(x.dtype))
    return ("shard", sd.geom, sd.band_rows, int(np.prod(mesh.devices.shape)),
            sd.halo, arr_sig, x_sig, tuple(y.shape), str(y.dtype), interpret)


def execute_sharded(sd: ShardedDispatch, x, y, *, mesh, interpret: bool,
                    stats=None, faults=None) -> jax.Array:
    """Run one sharded compiled kernel: a single jitted call, zero host
    descriptor work.  Shares the trace registry with the unsharded executor
    so ``CacheStats`` trace accounting stays one ledger."""
    if faults is not None:
        faults.probe("shard_exec",
                     detail=f"nd:{sd.n_devices}:{sd.operand_sharding}")
    # operands may sit on one device (an unsharded kernel produced them):
    # hand them to the mesh, whose devices the descriptor arrays span
    on_mesh = NamedSharding(mesh, P())
    y = jax.device_put(jnp.asarray(y), on_mesh)
    if x is not None:
        x = jax.device_put(jnp.asarray(x), on_mesh)
    key = _shard_signature(sd, x, y, mesh, interpret)
    with _dispatch._TRACE_LOCK:
        hit = key in _dispatch._TRACE_SEEN
        _dispatch._TRACE_SEEN.add(key)
    if stats is not None:
        if hit:
            stats.trace_cache_hits += 1
        else:
            stats.trace_builds += 1
    return _run_sharded(sd.geom, sd.band_rows, sd.arrays, x, y,
                        mesh=mesh, interpret=interpret, halo=sd.halo)
