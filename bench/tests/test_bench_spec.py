"""The benchmark's data: BENCHMARK.json, the configurations, the traffic
mixes, the peaks table and the cost arithmetic.  CPU only, no engine."""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import costs  # noqa: E402
import graphs  # noqa: E402
import harness  # noqa: E402
import load  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SPEC = harness.load_spec()


def _one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/main.py"]
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_and_units_are_legal():
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = ([c["name"] for c in SPEC["configs"]]
             + [w["name"] for w in SPEC["workloads"]]
             + [m["name"] for m in metrics])
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in SPEC["workloads"]]:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _one_line(c["why"]) and _one_line(c["source"])
        assert c["file"].startswith("bench/")
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert _one_line(w["why"]) and w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_end_to_end_bounds_and_sources():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.25
    for m in e2e.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _one_line(m["layer"])


def test_every_per_layer_metric_moves_a_metric_its_cells_report():
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        for cell in m["workloads"]:
            assert cell in cells
            reported = {e["name"] for e in harness.cell_metrics(
                SPEC, cell, traced=False)}
            assert m["moves"] in reported, (m["name"], cell)


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_reports_enough(cell):
    e2e = {m["name"] for m in harness.cell_metrics(SPEC, cell, False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.cell_metrics(SPEC, cell, True)


def test_every_name_has_its_file():
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        mod = harness._module(harness.BENCH / "metrics" / f"{m['name']}.py")
        assert callable(mod.read)
    for w in SPEC["workloads"]:
        harness.load_traffic(w["traffic"])
    for c in SPEC["configs"]:
        cfg = json.loads(open(os.path.join(ROOT, c["file"])).read())
        assert cfg["name"] == c["name"]
        harness.load_arch(cfg)


@pytest.mark.parametrize("name", ["gcn-co", "gin-co"])
def test_config_is_at_table_iv_size(name):
    cfg = harness.load_config(SPEC, name)
    d = cfg["dataset"]
    assert (d["vertices"], d["edges"], d["features"], d["classes"]) == (
        2708, 5429, 1433, 7)
    assert (cfg["hidden"], cfg["layers"], cfg["max_batch"]) == (16, 2, 8)
    assert (cfg["dtype"], cfg["precision"]) == ("float32", "highest")
    entry = next(c for c in SPEC["configs"] if c["name"] == name)
    assert entry["reduced"] == []


def test_graph_is_fixed_and_at_table_iv_size():
    g = graphs.make_graph(harness.load_config(SPEC, "gcn-co")["dataset"])
    assert g.features.shape == (2708, 1433)
    assert len(g.rows) == 8137
    assert graphs.block_count(g.rows, g.cols) == 5499
    assert abs((g.features != 0).mean() - 0.0127) < 1e-4


def test_kernel_counts_match_hand_sums():
    gcn = harness.load_arch(harness.load_config(SPEC, "gcn-co"))
    gin = harness.load_arch(harness.load_config(SPEC, "gin-co"))
    cfg = harness.load_config(SPEC, "gcn-co")
    k = {x.name: x for x in gcn.kernels(cfg, 8137, 1)}
    assert k["l1-update"].ops == 2 * 2708 * 1433 * 16
    assert k["l1-agg"].ops == 2 * 8137 * 16
    assert k["l2-update"].ops == 2 * 2708 * 16 * 7
    assert k["l2-agg"].ops == 2 * 8137 * 7
    assert k["l1-update"].bytes == 4 * (2708 * 1433 + 1433 * 16 + 2708 * 16)
    assert k["l1-agg"].bytes == 4 * (8137 * 2 + 2709 + 2 * 2708 * 16)
    assert costs.model_ops(gcn.kernels(cfg, 8137, 1)) == (
        2 * 2708 * 1433 * 16 + 2 * 8137 * 16 + 2 * 2708 * 16 * 7
        + 2 * 8137 * 7)
    assert costs.model_ops(gin.kernels(cfg, 8137, 1)) == (
        2 * 8137 * 1433 + 2 * 2708 * 1433 * 16 + 2 * 2 * 2708 * 16 * 16
        + 2 * 8137 * 16 + 2 * 2708 * 16 * 7)
    # a batch of 8 does 8 times the operations
    assert costs.model_ops(gcn.kernels(cfg, 8137, 8)) == 8 * costs.model_ops(
        gcn.kernels(cfg, 8137, 1))


def test_least_time_takes_the_slower_bound():
    peak = harness.load_peak("TPU v5 lite")
    assert peak["flops_per_s_bf16"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    k = costs.dense("x", 2708, 1433, 16)
    assert k.least_s(peak) == pytest.approx(k.bytes / 819e9)
    square = costs.dense("y", 8192, 8192, 8192)
    assert square.least_s(peak) == pytest.approx(square.ops / 197e12)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="for device kind 'TPU v9 imaginary'"):
        harness.load_peak("TPU v9 imaginary")


def test_kernel_rate_fit_is_pinned_per_device_kind():
    hw = harness.load_calibration("TPU v5 lite")
    assert hw.calibrated and not hw.fallback
    assert hw.spdmm_s_per_mac > 0 and hw.gemm_s_per_mac > 0
    with pytest.raises(KeyError, match="for device kind 'TPU v9 imaginary'"):
        harness.load_calibration("TPU v9 imaginary")


def test_pinned_fit_is_the_median_of_the_fits():
    import fit
    fits = [{"name": "m", "rate": r, "n_samples": 14} for r in (3.0, 1.0, 2.0)]
    assert fit.median_fit(fits) == {"name": "m", "rate": 2.0, "n_samples": 14}
    table = json.loads(open(os.path.join(BENCH, "calibration.json")).read())
    for kind, fits in table["fits"].items():
        assert table["devices"][kind] == fit.median_fit(fits)


def test_run_without_a_tpu_fails_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "main.py"), "--workload",
         "gcn-co.sat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_closed_loop_is_deterministic_by_seed():
    mix = harness.load_traffic("sat")
    a = load.closed_order(mix["pool"], mix["clients"],
                          np.random.default_rng([3, 1]))
    b = load.closed_order(mix["pool"], mix["clients"],
                          np.random.default_rng([3, 1]))
    c = load.closed_order(mix["pool"], mix["clients"],
                          np.random.default_rng([4, 1]))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.shape == (mix["clients"], mix["pool"])
    assert all(sorted(row) == list(range(mix["pool"])) for row in a)


@pytest.mark.parametrize("mix", ["sat", "dense-sat"])
def test_request_pool_is_deterministic_by_seed(mix):
    traffic = dict(harness.load_traffic(mix), pool=2)
    h0 = graphs.make_graph(dict(vertices=64, edges=128, features=32,
                                feature_density=0.1, graph_seed=1)).features
    a = load.make_pool(traffic, h0, np.random.default_rng([2**40 + 5, 0]))
    b = load.make_pool(traffic, h0, np.random.default_rng([2**40 + 5, 0]))
    c = load.make_pool(traffic, h0, np.random.default_rng([6, 0]))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    if traffic["features"] == "bow-noise":   # the sparsity pattern stays put
        assert all(np.array_equal(x != 0, h0 != 0) for x in a)
