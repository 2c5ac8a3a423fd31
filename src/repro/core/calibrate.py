"""Measured performance model — microbenchmark-calibrated Table I closed forms.

The paper's runtime mapping (Alg. 4) is only as good as its performance
model; Dynasparse's lesson is that dynamic mapping beats static thresholds
exactly when the model tracks the hardware it runs on.  ``VCK5000`` is
analytical by design (it reproduces the paper's tables), but the runtime
models (``TPUV5E`` and the other ``fallback=True`` entries of
``repro.core.perfmodel``) are hand-tuned guesses.  This module replaces the
guesses with measurements:

- :func:`calibrate` times the ACTUAL Pallas kernels the dispatcher issues —
  ``gemm_batch_scatter`` tiles (the dense queue), per-stored-block
  ``spdmm_fused``/``spmm_fused`` cost (the sparse queues), the on-device
  activation packer ``pack_activation_stripes``, and the per-launch
  dispatch floor — over a small shape/density sweep, then least-squares
  fits ``t = c0 + c1 * effective_MACs`` per engine and re-derives the
  :class:`~repro.core.perfmodel.HardwareModel` parameters (per-MAC rates,
  ``dispatch_overhead``, effective memory bandwidth) into a
  :class:`CalibratedModel`.
- The fitted bandwidth is cross-checked against
  :func:`repro.launch.roofline.hlo_cost` on the lowered XLA program of a
  reference GEMM (``roofline_bw_ratio`` — a consistency signal, ~O(1) when
  the fit and the HLO cost model agree about the same hardware).
- :func:`get_calibrated` persists the fit in a
  :class:`~repro.core.plancache.PlanCache` (and therefore in
  ``SharedPlanCache`` snapshots) keyed by (device kind, block, dtype, base
  model) with ``CacheStats.calib_builds/calib_hits`` accounting, plus an
  optional file snapshot (``REPRO_CALIBRATION_PATH`` — the CI cache
  artifact), so a restarted process replays ZERO measurements.

``DynasparseEngine(calibration="auto")`` resolves its analysis model through
this module whenever its hardware model is a ``fallback`` one; the Analyzer
and the compiled-path decline heuristics then follow measured device
timings instead of the guesses.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import os
import pickle
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.core.perfmodel import HardwareModel
from repro.kernels import ops

# number of microbenchmark kernel timings taken by THIS process — the
# bench/test observable for "a restart replays zero measurements"
_MEASUREMENTS = 0


def measurement_count() -> int:
    return _MEASUREMENTS


def reset_measurement_count() -> None:
    global _MEASUREMENTS
    _MEASUREMENTS = 0


@dataclasses.dataclass(frozen=True)
class CalibratedModel(HardwareModel):
    """A :class:`HardwareModel` whose rates were FIT from measured kernel
    timings.  The Table I closed forms are unchanged — only the parameters
    move — so the Analyzer/Scheduler consume it transparently.  Extra
    fields carry the fit's provenance and quality so a decision made on a
    calibrated model is auditable."""
    backend: str = ""          # compat.backend_kind() at measurement time
    block: int = 8             # Pallas block size the sweep used
    dtype: str = "float32"
    base: str = ""             # fallback model the frequencies came from
    n_samples: int = 0         # timed kernel invocations behind the fit
    gemm_s_per_mac: float = 0.0     # fitted marginal costs (seconds)
    spdmm_s_per_mac: float = 0.0    # ...per EFFECTIVE (stored-block) MAC
    spmm_s_per_mac: float = 0.0
    pack_s_per_slot: float = 0.0    # activation packer marginal slot cost
    fit_residual: float = 0.0       # max relative RMS across the fits
    roofline_flops: float = 0.0     # hlo_cost of the cross-check GEMM
    roofline_bytes: float = 0.0
    roofline_bw_ratio: float = 0.0  # hlo-implied achieved bw / fitted bw


def calibration_key(base: HardwareModel, block: int, dtype: str) -> tuple:
    """(device kind, block, dtype, base name) — the persistence key.  The
    device kind ("TPU v5 lite", "cpu", ...) comes first: measurements taken
    on one chip generation must never be replayed on another."""
    return (compat.device_kind(), int(block), str(dtype), base.name)


# ------------------------------------------------------------ measurement
def _time(fn, *, repeats: int) -> float:
    """Min-of-repeats wall time of ``fn()`` after one warmup call (the
    warmup absorbs tracing/compilation, which is launch overhead's job to
    model only through the dispatch floor, not the marginal rates)."""
    global _MEASUREMENTS
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    _MEASUREMENTS += 1
    return best


def _measure_gemm(block: int, np_dtype, interpret: bool, repeats: int,
                  rng) -> list[dict]:
    """Dense-queue samples: ``gemm_batch_scatter`` with T canvas tiles —
    exactly the launch the compiled dispatch issues for the DTQ."""
    m = k = n = 4 * block
    out = []
    for T in (1, 2, 4):
        x = jnp.asarray(rng.normal(size=(T, m, k)).astype(np_dtype))
        y = jnp.asarray(rng.normal(size=(T, k, n)).astype(np_dtype))
        rows = jnp.arange(T, dtype=jnp.int32)
        cols = jnp.zeros(T, dtype=jnp.int32)
        z = jnp.zeros((T * m, n), jnp.float32)
        t = _time(lambda: ops.gemm_batch_scatter(
            x, y, rows, cols, z, interpret=interpret), repeats=repeats)
        out.append({"kind": "gemm", "macs": T * m * k * n, "t": t})
    return out


def _measure_spdmm(block: int, np_dtype, interpret: bool, repeats: int,
                   rng) -> list[dict]:
    """Sparse-queue samples: ``spdmm_fused`` over E stored-block entries —
    the per-stored-block cost the block-skip closed form needs."""
    B, bn, ncb = block, 4 * block, 4
    y = jnp.asarray(rng.normal(size=(ncb * B, bn)).astype(np_dtype))
    out = []
    for E in (4, 16, 48):
        pool = jnp.asarray(rng.normal(size=(E, B, B)).astype(np_dtype))
        ids = jnp.arange(E, dtype=jnp.int32)
        y_rows = jnp.asarray(np.arange(E, dtype=np.int32) % ncb)
        zeros = jnp.zeros(E, dtype=jnp.int32)
        first = jnp.ones(E, dtype=jnp.int32)
        t = _time(lambda: ops.spdmm_fused(
            pool, y, ids, y_rows, ids, zeros, first,
            block_size=B, bn=bn, m_pad=E * B, interpret=interpret),
            repeats=repeats)
        out.append({"kind": "spdmm", "macs": E * B * B * bn, "t": t})
    return out


def _measure_spmm(block: int, np_dtype, interpret: bool, repeats: int,
                  rng) -> list[dict]:
    """Sparse-queue samples: ``spmm_fused`` over E (A block, Y block)
    triples."""
    B = block
    y_pool = jnp.asarray(rng.normal(size=(8, B, B)).astype(np_dtype))
    out = []
    for E in (4, 16, 48):
        pool = jnp.asarray(rng.normal(size=(E, B, B)).astype(np_dtype))
        ids = jnp.arange(E, dtype=jnp.int32)
        y_ids = jnp.asarray(np.arange(E, dtype=np.int32) % 8)
        zeros = jnp.zeros(E, dtype=jnp.int32)
        first = jnp.ones(E, dtype=jnp.int32)
        t = _time(lambda: ops.spmm_fused(
            pool, y_pool, ids, y_ids, ids, zeros, first,
            block_size=B, m_pad=E * B, n_pad=B, interpret=interpret),
            repeats=repeats)
        out.append({"kind": "spmm", "macs": E * B * B * B, "t": t})
    return out


def _measure_pack(block: int, np_dtype, repeats: int, rng) -> list[dict]:
    """Activation-packer samples: the traceable
    ``pack_activation_stripes`` jitted alone, swept over slot counts."""
    B = block
    out = []
    for S, R, C, cap in ((2, 4, 4, 4), (4, 4, 8, 8)):
        x = jnp.asarray(rng.normal(size=(S * R * B, C * B)).astype(np_dtype))
        pk = jax.jit(functools.partial(
            ops.pack_activation_stripes, block=B, n_stripes=S, slot_rows=R,
            n_block_cols=C, capacity=cap, eps=0.0))
        t = _time(lambda: pk(x), repeats=repeats)
        out.append({"kind": "pack", "slots": S * cap, "t": t})
    return out


def _measure_dispatch_floor(block: int, np_dtype, interpret: bool,
                            repeats: int, rng) -> float:
    """Per-launch dispatch floor: the smallest possible kernel's wall time
    is almost entirely launch overhead."""
    B = block
    x = jnp.asarray(rng.normal(size=(1, B, B)).astype(np_dtype))
    y = jnp.asarray(rng.normal(size=(1, B, B)).astype(np_dtype))
    z = jnp.zeros((B, B), jnp.float32)
    idx = jnp.zeros(1, dtype=jnp.int32)
    return _time(lambda: ops.gemm_batch_scatter(
        x, y, idx, idx, z, interpret=interpret), repeats=repeats)


def _measure_membw(np_dtype, repeats: int) -> float:
    """Effective memory bandwidth from a jitted streaming op (read + write
    one large buffer)."""
    a = jnp.zeros((1024, 1024), np_dtype)
    f = jax.jit(lambda v: v + 1)
    t = _time(lambda: f(a), repeats=repeats)
    return 2.0 * a.size * a.dtype.itemsize / max(t, 1e-9)


def _fit_linear(samples: list[dict], xkey: str = "macs"
                ) -> tuple[float, float, float]:
    """Least-squares ``t = c0 + c1 * x`` with nonnegativity clamps; returns
    (c0, c1, relative RMS residual)."""
    t = np.array([s["t"] for s in samples], dtype=np.float64)
    x = np.array([s[xkey] for s in samples], dtype=np.float64)
    A = np.stack([np.ones_like(x), x], axis=1)
    coef, *_ = np.linalg.lstsq(A, t, rcond=None)
    c0, c1 = float(coef[0]), float(coef[1])
    if c1 <= 0.0:
        # overhead-dominated sweep: the marginal slope is below measurement
        # noise.  Attribute the largest sample's whole time as marginal
        # cost — a conservative upper bound — rather than fitting a free
        # (or negative-cost) engine that the Analyzer would then always pick.
        i = int(np.argmax(x))
        c0, c1 = 0.0, float(t[i] / x[i])
    c0 = max(c0, 0.0)
    c1 = max(c1, 1e-18)
    pred = c0 + c1 * x
    resid = float(np.sqrt(np.mean(((pred - t) / np.maximum(t, 1e-12)) ** 2)))
    return c0, c1, resid


def _roofline_crosscheck(np_dtype, membw_fit: float, repeats: int
                         ) -> tuple[float, float, float]:
    """Lower a reference GEMM, cost it with ``roofline.hlo_cost``, time it,
    and compare the HLO-implied achieved bandwidth with the fitted one.
    Never fatal — a backend whose HLO text the parser cannot read reports
    zeros instead of failing calibration."""
    try:
        from repro.launch import roofline
        a = jnp.zeros((256, 256), np_dtype)
        b = jnp.zeros((256, 256), np_dtype)
        fn = jax.jit(lambda u, v: jnp.dot(
            u, v, preferred_element_type=jnp.float32))
        cost = roofline.lowered_cost(fn, a, b)
        t = _time(lambda: fn(a, b), repeats=repeats)
        implied_bw = float(cost["bytes"]) / max(t, 1e-12)
        return (float(cost["flops"]), float(cost["bytes"]),
                implied_bw / max(membw_fit, 1e-9))
    except Exception:
        return 0.0, 0.0, 0.0


def calibrate(base: HardwareModel, *, block: int = 8,
              dtype: str = "float32", interpret: bool | None = None,
              repeats: int = 2, seed: int = 0) -> CalibratedModel:
    """Run the microbenchmark sweep ONCE and fit a :class:`CalibratedModel`.

    The base model contributes its frequencies (rates are re-derived from
    the fitted marginal costs at those frequencies, so the closed forms
    keep their Table I shape) and its ``skip_block`` granularity; every
    rate, the dispatch overhead and the memory bandwidth are replaced by
    measurements.  ``n_sparse_units`` becomes 1 — the measured sparse path
    is one fused kernel stream, not the paper's 8 ALU arrays.
    """
    interpret = ops.default_interpret() if interpret is None else interpret
    np_dtype = np.dtype(dtype)
    rng = np.random.default_rng(seed)
    n0 = measurement_count()

    gemm_s = _measure_gemm(block, np_dtype, interpret, repeats, rng)
    spdmm_s = _measure_spdmm(block, np_dtype, interpret, repeats, rng)
    spmm_s = _measure_spmm(block, np_dtype, interpret, repeats, rng)
    pack_s = _measure_pack(block, np_dtype, repeats, rng)
    floor = _measure_dispatch_floor(block, np_dtype, interpret, repeats, rng)
    membw = _measure_membw(np_dtype, repeats)

    c0_g, c1_g, r_g = _fit_linear(gemm_s)
    c0_d, c1_d, r_d = _fit_linear(spdmm_s)
    c0_m, c1_m, r_m = _fit_linear(spmm_s)
    _, c1_p, r_p = _fit_linear(pack_s, xkey="slots")
    # the dispatch floor and the fitted intercepts estimate the same launch
    # bubble from different sweeps; take the most pessimistic
    overhead = max(floor, c0_g, c0_d, c0_m)

    rl_flops, rl_bytes, rl_ratio = _roofline_crosscheck(
        np_dtype, membw, repeats)

    return CalibratedModel(
        name=(f"{base.name}+calib[{compat.device_kind()}"
              f",b{block},{dtype}]"),
        f_dense=base.f_dense,
        dense_macs_per_cycle=1.0 / (c1_g * base.f_dense),
        f_sparse=base.f_sparse,
        spdmm_macs_per_cycle=1.0 / (c1_d * base.f_sparse),
        spmm_macs_per_cycle=1.0 / (c1_m * base.f_sparse),
        n_sparse_units=1,
        mem_bw=membw,
        bytes_per_elem=int(np_dtype.itemsize),
        dispatch_overhead=overhead,
        skip_block=base.skip_block,
        fallback=False,
        calibrated=True,
        backend=compat.backend_kind(),
        block=int(block),
        dtype=str(dtype),
        base=base.name,
        n_samples=measurement_count() - n0,
        gemm_s_per_mac=c1_g,
        spdmm_s_per_mac=c1_d,
        spmm_s_per_mac=c1_m,
        pack_s_per_slot=c1_p,
        fit_residual=float(max(r_g, r_d, r_m, r_p)),
        roofline_flops=rl_flops,
        roofline_bytes=rl_bytes,
        roofline_bw_ratio=rl_ratio,
    )


# ------------------------------------------------------------- persistence
SNAPSHOT_ENV = "REPRO_CALIBRATION_PATH"


def save_snapshot(path: str, models: dict[tuple, CalibratedModel]) -> None:
    """Write a calibration snapshot (the CI cache artifact).  Plain pickle
    of {calibration_key: CalibratedModel} — every field is a host scalar.

    Atomic: pickled to a same-directory temp file then ``os.replace``d into
    place, so a crash mid-save can never leave a truncated snapshot for the
    next process to choke on (it keeps the previous snapshot instead)."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "wb") as f:
            pickle.dump({"version": 1, "models": dict(models)}, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def load_snapshot(path: str) -> dict[tuple, CalibratedModel]:
    with open(path, "rb") as f:
        payload = pickle.load(f)
    if payload.get("version") != 1:
        raise ValueError(
            f"unsupported calibration snapshot version "
            f"{payload.get('version')!r}")
    return dict(payload["models"])


def get_calibrated(cache, base: HardwareModel, *, block: int = 8,
                   dtype: str = "float32", interpret: bool | None = None,
                   repeats: int = 2,
                   snapshot_path: str | None = None) -> CalibratedModel:
    """Get-or-measure the calibration for (device kind, block, dtype, base).

    Resolution order: the plan cache (``calib_hits`` — zero work), then the
    file snapshot (``snapshot_path`` or ``$REPRO_CALIBRATION_PATH`` — zero
    measurements, counted as a build), then a fresh :func:`calibrate` sweep
    whose result is written back to both.  A ``SharedPlanCache.save``/
    ``load`` round-trip therefore replays restarts with zero re-measures.
    """
    key = calibration_key(base, block, dtype)

    def compute() -> CalibratedModel:
        path = snapshot_path or os.environ.get(SNAPSHOT_ENV)
        if path and os.path.exists(path):
            try:
                m = load_snapshot(path).get(key)
                if m is not None:
                    return m
            except Exception as exc:
                # unreadable (corrupt/truncated/wrong-version) snapshot:
                # a logged cold start — fall through to measuring.  The
                # counter makes the degradation observable instead of a
                # silently slower restart.
                cache.stats.snapshot_errors += 1
                logging.getLogger(__name__).warning(
                    "calibration snapshot %s unusable (%s: %s) — "
                    "re-measuring", path, type(exc).__name__, exc)
        m = calibrate(base, block=block, dtype=dtype, interpret=interpret,
                      repeats=repeats)
        if path:
            try:
                snap = load_snapshot(path) if os.path.exists(path) else {}
            except Exception:
                snap = {}
            try:
                snap[key] = m
                save_snapshot(path, snap)
            except Exception:
                pass   # read-only FS: the in-process cache still has it
        return m

    return cache.calibration(key, compute)
