"""GNN model correctness: engine inference == pure-jnp reference."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DynasparseEngine
from repro.data.graphs import load_graph, DATASETS
from repro.models import gnn

SMALL_SCALE = 0.02   # shrink datasets for CPU functional runs


@pytest.mark.parametrize("model", gnn.MODELS)
def test_model_matches_reference_small(model):
    g = load_graph("CO", scale=SMALL_SCALE)
    h = g.features_dense
    params = gnn.init_params(model, h.shape[1], 16, g.stats.classes)
    eng = DynasparseEngine(tile_m=32, tile_n=16)
    logits, report = gnn.run_inference(model, eng, g.adj, h, params)
    ref = gnn.run_reference(model, g.adj, h, params)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)
    assert report.hardware_time > 0
    assert not np.isnan(np.asarray(logits)).any()


@pytest.mark.parametrize("model", gnn.MODELS)
def test_model_literal_execution_small(model):
    """Literal per-queue Pallas execution end-to-end (interpret mode)."""
    g = load_graph("CI", scale=0.01)
    h = g.features_dense
    params = gnn.init_params(model, h.shape[1], 8, g.stats.classes)
    eng = DynasparseEngine(tile_m=16, tile_n=8, literal=True)
    logits, _ = gnn.run_inference(model, eng, g.adj, h, params)
    ref = gnn.run_reference(model, g.adj, h, params)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


def test_dynamic_latency_never_worse_than_baselines():
    g = load_graph("CO", scale=SMALL_SCALE)
    h = g.features_dense
    params = gnn.init_params("GCN", h.shape[1], 16, g.stats.classes)
    times = {}
    for mode in ("dynamic", "sparse_only", "dense_only"):
        eng = DynasparseEngine(mode=mode, tile_m=32, tile_n=16)
        _, report = gnn.run_inference("GCN", eng, g.adj, h, params)
        times[mode] = report.hardware_time
    assert times["dynamic"] <= times["sparse_only"] * 1.0001
    assert times["dynamic"] <= times["dense_only"] * 1.0001


def test_dataset_stats_match_table_iv():
    for name, st in DATASETS.items():
        g = load_graph(name, scale=0.01) if name in ("NE", "RE") else \
            load_graph(name, scale=0.05)
        # density of generated features tracks Table IV
        assert g.feature_density == pytest.approx(st.density_h, rel=0.5, abs=0.002)


def test_full_scale_small_datasets_load():
    g = load_graph("CO")
    assert g.stats.vertices == 2708
    assert g.adj.nnz == 5429 + 2708  # edges + self loops
    assert g.features_dense.shape == (2708, 1433)
    # adjacency density ~ Table IV (0.14%)
    assert g.adj.density == pytest.approx(0.0014, rel=0.5)
