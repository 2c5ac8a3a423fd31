"""The check that decides ``correct``, at a size a CPU test run can hold.

- The control (the reference in the program's place, three bfloat16 passes
  where the configuration states float32 at ``highest``) fails the limit,
  and the same reference at float32 ``highest`` passes it; driven as
  ``control.py`` drives it, it comes out not correct.
- A whole run of the harness, with the look for a chip skipped, comes out
  correct on the served program, and not correct when the program's answer
  is altered where it is produced, or when it serves only the first half
  of each micro-batch and hands those answers to the second half too.
- Set-up warms every micro-batch size, so that a partial batch in the
  window compiles nothing.
"""
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
import control  # noqa: E402
import harness  # noqa: E402
import load  # noqa: E402

SPEC = harness.load_spec()


def _tiny(name: str) -> dict:
    """The configuration ``name`` with a 96-vertex graph and 64 features."""
    cfg = harness.load_config(SPEC, name)
    cfg["dataset"] = dict(cfg["dataset"], vertices=96, edges=192,
                          features=64, feature_density=0.05)
    cfg["max_batch"] = 2
    return cfg


def _answers(s, forward) -> list:
    recs = []
    for i in range(len(s.pool)):
        r = load.Record(idx=i, t_due=0.0)
        r.logits = np.asarray(forward(s.pool[i]))
        recs.append(r)
    return recs


@pytest.mark.parametrize("name", ["gcn-co", "gin-co"])
def test_control_fails_and_float32_passes(name):
    import jax
    import jax.numpy as jnp
    s = harness.prepare(_tiny(name), dict(harness.load_traffic("sat"),
                                          pool=3), seed=2**33 + 1)
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


@pytest.mark.parametrize("name", ["gcn-co", "gin-co"])
def test_control_run_is_not_correct(name, monkeypatch):
    traffic = dict(harness.load_traffic("sat"), clients=2, pool=2)
    monkeypatch.setattr(harness, "load_traffic", lambda mix: traffic)
    cell = {"name": "tiny", "config": name, "traffic": "sat", "chips": 1}
    out = control.readings(cell, _tiny(name), 2**33 + 3, 0.5)
    assert out["attempted"] > 0
    assert out["checks"]["failed"]["value"] == 0
    assert out["correct"] is False


def test_verdict_holds_every_number_to_its_limit():
    assert check.verdict({"a": {"value": 0, "limit": 0},
                          "b": {"value": 1e-7, "limit": 1e-6}})
    assert not check.verdict({"a": {"value": 1, "limit": 0},
                              "b": {"value": 1e-7, "limit": 1e-6}})
    assert not check.verdict({"b": {"value": float("inf"), "limit": 1e-6}})


def test_compile_counter_names_the_programs():
    import jax
    import jax.numpy as jnp
    counter = harness.CompileCounter()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(5.0))
    assert counter.count == len(counter.names) >= 1
    assert counter.seconds > 0


def _server(s):
    from repro.core.perfmodel import runtime_fallback
    # a fixed model: the calibration sweep is for the chip
    hw = dataclasses.replace(runtime_fallback(), fallback=False)
    return harness.program_server(s, calibration=hw)


def _altered(z):
    return z.at[0, 0].add(1e-3)


def _half_batch(z):
    import jax.numpy as jnp
    half = z[:, :z.shape[1] // 2]
    return jnp.concatenate([half, half], axis=1)


@pytest.mark.parametrize("fault", [None, _altered, _half_batch],
                         ids=["sound", "answer-altered", "half-batch"])
def test_run_is_judged(fault, monkeypatch):
    from repro.models import gnn
    if fault is not None:
        served = gnn.CompiledModel.__call__
        monkeypatch.setattr(gnn.CompiledModel, "__call__",
                            lambda self, h: fault(served(self, h)))
    traffic = dict(harness.load_traffic("sat"), clients=4, pool=4,
                   warm_batch_sizes=[2])
    monkeypatch.setattr(harness, "load_traffic", lambda name: traffic)
    cell = {"name": "tiny", "config": "gcn-co", "traffic": "sat", "chips": 1}
    metrics = harness.cell_metrics(SPEC, "gcn-co.sat", traced=False)
    out = harness.run_cell(cell, _tiny("gcn-co"), 7, 1.0, False, metrics,
                           time.perf_counter(), server=_server)
    json.dumps(out)
    assert out["correct"] is (fault is None)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"req_per_s", "setup_s"}


@pytest.mark.parametrize("sizes", [[3], [1, 2, 3]],
                         ids=["full-batch-only", "every-size"])
def test_warm_up_covers_partial_batches(sizes):
    cfg = dict(_tiny("gcn-co"), max_batch=3)
    s = harness.prepare(cfg, dict(harness.load_traffic("sat"), pool=3,
                                  warm_batch_sizes=sizes), seed=5)
    srv = _server(s)
    infer = harness._infer(srv)
    compiles = harness.CompileCounter()

    async def go():
        await harness.warm(infer, s, time.perf_counter())
        c0 = compiles.count
        await asyncio.gather(infer(s.pool[0]), infer(s.pool[1]))
        return compiles.names[c0:]

    try:
        names = asyncio.run(go())
    finally:
        srv.close()
    assert srv.stats.requests[-1].batch_size == 2       # a partial batch
    if sizes == [3]:
        assert any("concatenate" in n for n in names), names
    else:
        assert names == []
