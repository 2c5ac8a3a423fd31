"""Calibration subsystem tests (ISSUE 7 tentpole): least-squares fit
recovery, PlanCache/SharedPlanCache persistence with calib counters and
zero re-measures across a simulated restart, snapshot-file round-trips,
and engine auto-calibration gating on ``fallback`` models."""
import numpy as np
import pytest

from repro import compat
from repro.core import calibrate
from repro.core.engine import DynasparseEngine
from repro.core.perfmodel import VCK5000, runtime_fallback
from repro.core.plancache import PlanCache
from repro.core.primitives import SparseCOO
from repro.serving.cache import SharedPlanCache


@pytest.fixture(autouse=True)
def _no_snapshot_env(monkeypatch):
    monkeypatch.delenv(calibrate.SNAPSHOT_ENV, raising=False)


def _fake_model(base=None, **over):
    base = base or runtime_fallback("cpu")
    kw = dict(
        name=f"{base.name}+calib[test,b8,float32]",
        f_dense=base.f_dense, dense_macs_per_cycle=1e3,
        f_sparse=base.f_sparse, spdmm_macs_per_cycle=1e3,
        spmm_macs_per_cycle=1e3, n_sparse_units=1, mem_bw=1e9,
        bytes_per_elem=4, dispatch_overhead=1e-4, skip_block=base.skip_block,
        calibrated=True, backend=compat.backend_kind(), block=8,
        dtype="float32", base=base.name, n_samples=14)
    kw.update(over)
    return calibrate.CalibratedModel(**kw)


def test_fit_linear_recovers_synthetic_coefficients():
    c0, c1 = 2e-3, 3e-9
    samples = [{"t": c0 + c1 * m, "macs": m}
               for m in (1e4, 5e4, 2e5, 1e6)]
    f0, f1, resid = calibrate._fit_linear(samples)
    assert f0 == pytest.approx(c0, rel=1e-6)
    assert f1 == pytest.approx(c1, rel=1e-6)
    assert resid < 1e-6


def test_fit_linear_clamps_nonnegative():
    # decreasing times would fit a negative slope: clamp, don't extrapolate
    samples = [{"t": 1e-3 - 1e-10 * m, "macs": m} for m in (1e4, 1e6)]
    c0, c1, _ = calibrate._fit_linear(samples)
    assert c0 >= 0.0 and c1 > 0.0


def test_get_calibrated_caches_and_counts(monkeypatch):
    calls = []
    fake = _fake_model()
    monkeypatch.setattr(calibrate, "calibrate",
                        lambda *a, **k: calls.append(1) or fake)
    cache = PlanCache()
    base = runtime_fallback("cpu")
    m1 = calibrate.get_calibrated(cache, base, block=8)
    m2 = calibrate.get_calibrated(cache, base, block=8)
    assert m1 is fake and m2 is fake
    assert len(calls) == 1
    assert cache.stats.calib_builds == 1 and cache.stats.calib_hits == 1
    assert cache.calibration_count() == 1


def test_calibration_key_binds_backend_block_dtype(monkeypatch):
    base = runtime_fallback("cpu")
    k = calibrate.calibration_key(base, 8, "float32")
    assert k == (compat.device_kind(), 8, "float32", base.name)
    assert k != calibrate.calibration_key(base, 16, "float32")
    assert k != calibrate.calibration_key(VCK5000, 8, "float32")
    # another chip generation never replays this one's measurements
    monkeypatch.setattr(compat, "device_kind", lambda: "TPU v4")
    assert calibrate.calibration_key(base, 8, "float32") != k


def test_snapshot_file_roundtrip_and_replay(tmp_path, monkeypatch):
    base = runtime_fallback("cpu")
    key = calibrate.calibration_key(base, 8, "float32")
    fake = _fake_model(base)
    path = str(tmp_path / "calib" / "snapshot.pkl")
    calibrate.save_snapshot(path, {key: fake})
    loaded = calibrate.load_snapshot(path)
    assert loaded[key] == fake

    # a fresh process (fresh cache) must replay from the snapshot file with
    # ZERO measurements: a real sweep would blow through this sentinel
    def boom(*a, **k):
        raise AssertionError("measured despite snapshot")
    monkeypatch.setattr(calibrate, "calibrate", boom)
    cache = PlanCache()
    n0 = calibrate.measurement_count()
    m = calibrate.get_calibrated(cache, base, block=8, snapshot_path=path)
    assert m == fake
    assert calibrate.measurement_count() == n0
    assert cache.stats.calib_builds == 1   # built from file, not measured


def test_snapshot_env_var_and_write_back(tmp_path, monkeypatch):
    base = runtime_fallback("cpu")
    fake = _fake_model(base)
    monkeypatch.setattr(calibrate, "calibrate", lambda *a, **k: fake)
    path = str(tmp_path / "snapshot.pkl")
    monkeypatch.setenv(calibrate.SNAPSHOT_ENV, path)
    m = calibrate.get_calibrated(PlanCache(), base, block=8)
    assert m is fake
    # the measurement was written back to the env-pointed snapshot
    key = calibrate.calibration_key(base, 8, "float32")
    assert calibrate.load_snapshot(path)[key] == fake


def test_snapshot_rejects_unknown_version(tmp_path):
    import pickle
    path = tmp_path / "bad.pkl"
    path.write_bytes(pickle.dumps({"version": 99, "models": {}}))
    with pytest.raises(ValueError, match="snapshot version"):
        calibrate.load_snapshot(str(path))


def test_shared_cache_restart_replays_zero_measurements(
        tmp_path, monkeypatch):
    """SharedPlanCache.save/load carries the calibration entry: after a
    simulated restart the engine's model resolves with calib_builds == 0
    and no microbenchmark runs."""
    base = runtime_fallback("cpu")
    fake = _fake_model(base)
    monkeypatch.setattr(calibrate, "calibrate", lambda *a, **k: fake)
    cache = SharedPlanCache()
    calibrate.get_calibrated(cache, base, block=8)
    assert cache.calibration_count() == 1
    snap = str(tmp_path / "cache.pkl")
    cache.save(snap)

    def boom(*a, **k):
        raise AssertionError("measured despite warm cache")
    monkeypatch.setattr(calibrate, "calibrate", boom)
    fresh = SharedPlanCache()
    fresh.load(snap)
    assert fresh.calibration_count() == 1
    n0 = calibrate.measurement_count()
    m = calibrate.get_calibrated(fresh, base, block=8)
    assert m == fake
    assert calibrate.measurement_count() == n0
    assert fresh.stats.calib_builds == 0 and fresh.stats.calib_hits == 1


def _toy_coo(rng, n=64, deg=4):
    rows = np.repeat(np.arange(n), deg)
    cols = rng.integers(0, n, size=n * deg)
    coo = np.unique(np.stack([rows, cols], 1), axis=0)
    return SparseCOO(shape=(n, n),
                     rows=np.asarray(coo[:, 0], np.int32),
                     cols=np.asarray(coo[:, 1], np.int32),
                     vals=np.ones(len(coo), np.float32))


def test_engine_auto_calibration_gates_on_fallback(monkeypatch):
    """Analytical models are never calibrated away; fallback models resolve
    through get_calibrated exactly once per engine; the effective model's
    name lands in the plan key, so static and calibrated plans coexist."""
    fake = _fake_model()
    calls = []
    monkeypatch.setattr(calibrate, "calibrate",
                        lambda *a, **k: calls.append(1) or fake)

    eng = DynasparseEngine(interpret=True)          # VCK5000: analytical
    assert eng.runtime_hw() is VCK5000
    assert not calls

    fb = runtime_fallback("cpu")
    eng2 = DynasparseEngine(fb, interpret=True)
    assert eng2.runtime_hw() is fake
    assert eng2.runtime_hw() is fake                # resolved once
    assert len(calls) == 1
    assert eng2.cache.stats.calib_builds == 1

    # calibration="off" trusts the fallback constants as given
    eng3 = DynasparseEngine(fb, interpret=True, calibration="off")
    assert eng3.runtime_hw() is fb

    # an explicit model wins over both
    eng4 = DynasparseEngine(fb, interpret=True, calibration=VCK5000)
    assert eng4.runtime_hw() is VCK5000


def test_engine_plan_key_uses_effective_model(monkeypatch):
    fake = _fake_model()
    monkeypatch.setattr(calibrate, "calibrate", lambda *a, **k: fake)
    rng = np.random.default_rng(0)
    adj = _toy_coo(rng)
    y = rng.normal(size=(64, 16)).astype(np.float32)
    fb = runtime_fallback("cpu")
    cache = PlanCache()
    eng_cal = DynasparseEngine(fb, tile_m=16, tile_n=8, literal=True,
                               interpret=True, cache=cache)
    eng_off = DynasparseEngine(fb, tile_m=16, tile_n=8, literal=True,
                               interpret=True, cache=cache,
                               calibration="off")
    eng_cal.plan(adj, y)
    eng_off.plan(adj, y)
    # two distinct plans in one cache: the calibrated and the static model
    # have different names, so neither shadows the other
    assert cache.plan_count() == 2
