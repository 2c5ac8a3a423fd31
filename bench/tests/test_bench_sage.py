"""The ``sage-fl`` configuration: its size, its reference, its kernels' costs
and its check, at a size a CPU test run can hold, and its graph at full
size.  CPU only."""
import asyncio
import dataclasses
import json
import os
import sys
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import check  # noqa: E402
import costs  # noqa: E402
import graphs  # noqa: E402
import harness  # noqa: E402
import load  # noqa: E402

SPEC = harness.load_spec()


def _tiny() -> dict:
    """``sage-fl`` on a 96-vertex graph.  256 features keep both layers'
    fan-in above their fan-out, so the served order (transform first) is
    the one exercised, at the configuration's own hidden width."""
    cfg = harness.load_config(SPEC, "sage-fl")
    cfg["dataset"] = dict(cfg["dataset"], vertices=96, edges=192,
                          features=256, feature_density=0.05)
    cfg["max_batch"] = 2
    return cfg


def _answers(s, forward) -> list:
    recs = []
    for i in range(len(s.pool)):
        r = load.Record(idx=i, t_due=0.0)
        r.logits = np.asarray(forward(s.pool[i]))
        recs.append(r)
    return recs


def test_config_is_at_table_iv_size():
    cfg = harness.load_config(SPEC, "sage-fl")
    d = cfg["dataset"]
    assert (d["vertices"], d["edges"], d["features"], d["classes"],
            d["feature_density"]) == (89250, 899756, 500, 7, 0.46)
    assert (cfg["hidden"], cfg["layers"], cfg["max_batch"]) == (128, 2, 8)
    assert (cfg["dtype"], cfg["precision"]) == ("float32", "highest")
    assert cfg["arch"] == "sage"
    entry = next(c for c in SPEC["configs"] if c["name"] == "sage-fl")
    assert entry["reduced"] == []
    assert entry["source"] == "https://arxiv.org/abs/1706.02216"
    cell = next(w for w in SPEC["workloads"] if w["name"] == "sage-fl.sat")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sage-fl", "sat", 1)


def test_graph_is_fixed_and_at_table_iv_size():
    g = graphs.make_graph(harness.load_config(SPEC, "sage-fl")["dataset"])
    assert g.features.shape == (89250, 500)
    assert len(g.rows) == 989006                  # edges + self-loops, merged
    assert graphs.block_count(g.rows, g.cols) == 900699
    assert abs((g.features != 0).mean() - 0.46) < 1e-4


def test_kernel_counts_match_hand_sums():
    cfg = harness.load_config(SPEC, "sage-fl")
    sage = harness.load_arch(cfg)
    n, nnz = 89250, 989006
    k = {x.name: x for x in sage.kernels(cfg, nnz, 1)}
    assert list(k) == ["l1-update", "l1-agg", "l2-update", "l2-agg"]
    assert k["l1-update"].ops == 2 * n * 500 * 256
    assert k["l1-agg"].ops == 2 * nnz * 128
    assert k["l2-update"].ops == 2 * n * 128 * 14
    assert k["l2-agg"].ops == 2 * nnz * 7
    assert k["l1-update"].bytes == 4 * (n * 500 + 500 * 256 + n * 256)
    assert k["l1-agg"].bytes == 4 * (nnz * 2 + (n + 1) + 2 * n * 128)
    assert k["l2-update"].bytes == 4 * (n * 128 + 128 * 14 + n * 14)
    assert k["l2-agg"].bytes == 4 * (nnz * 2 + (n + 1) + 2 * n * 7)
    # the fused transform does the operations of the two it replaces
    assert costs.model_ops(sage.kernels(cfg, nnz, 1)) == (
        2 * (2 * n * 500 * 128) + 2 * nnz * 128
        + 2 * (2 * n * 128 * 7) + 2 * nnz * 7)
    assert costs.model_ops(sage.kernels(cfg, nnz, 8)) == 8 * costs.model_ops(
        sage.kernels(cfg, nnz, 1))


def test_reference_keeps_the_two_transforms_apart():
    cfg = _tiny()
    sage = harness.load_arch(cfg)
    shapes = sage.weight_shapes(cfg)
    assert shapes == {"Ws1": (256, 128), "Wn1": (256, 128),
                      "Ws2": (128, 7), "Wn2": (128, 7)}
    rng = np.random.default_rng(0)
    w = {name: rng.normal(size=s) for name, s in shapes.items()}
    seen = []

    def dot(x, y):
        seen.append(next(name for name, v in w.items() if v is y))
        return x @ y
    sage.forward(lambda x: x, dot, lambda x: np.maximum(x, 0.0),
                 rng.normal(size=(5, 256)), w)
    assert seen == ["Ws1", "Wn1", "Ws2", "Wn2"]


def test_control_fails_and_float32_passes():
    import jax
    import jax.numpy as jnp
    s = harness.prepare(_tiny(), dict(harness.load_traffic("sat"), pool=3),
                        seed=2**33 + 5)
    control = check.control_infer(s)
    got = check.judge(s, _answers(s, lambda h: asyncio.run(control(h))))
    assert got["failed"]["value"] == 0
    assert got["logit_err"]["value"] > got["logit_err"]["limit"]

    g = s.graph
    a = jnp.zeros((g.n, g.n), jnp.float32).at[g.rows, g.cols].add(g.vals)

    def dot(x, y):
        return jnp.dot(x, y, precision="highest")
    exact = check.judge(s, _answers(s, lambda h: s.arch.forward(
        lambda x: dot(a, x), dot, jax.nn.relu, jnp.asarray(h), s.weights)))
    assert exact["logit_err"]["value"] <= exact["logit_err"]["limit"]


def _server(s):
    from repro.core.perfmodel import runtime_fallback
    # a fixed model: the calibration sweep is for the chip
    hw = dataclasses.replace(runtime_fallback(), fallback=False)
    return harness.program_server(s, calibration=hw)


@pytest.mark.parametrize("altered", [False, True],
                         ids=["sound", "answer-altered"])
def test_run_is_judged(altered, monkeypatch):
    from repro.models import gnn
    if altered:
        served = gnn.CompiledModel.__call__
        monkeypatch.setattr(gnn.CompiledModel, "__call__",
                            lambda self, h: served(self, h).at[0, 0].add(
                                1e-3))
    traffic = dict(harness.load_traffic("sat"), clients=4, pool=4,
                   warm_batch_sizes=[2])
    monkeypatch.setattr(harness, "load_traffic", lambda name: traffic)
    cell = {"name": "tiny", "config": "sage-fl", "traffic": "sat",
            "chips": 1}
    metrics = harness.cell_metrics(SPEC, "sage-fl.sat", traced=False)
    out = harness.run_cell(cell, _tiny(), 2**33 + 7, 1.0, False, metrics,
                           time.perf_counter(), server=_server)
    json.dumps(out)
    assert out["correct"] is not altered
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"req_per_s", "setup_s"}
