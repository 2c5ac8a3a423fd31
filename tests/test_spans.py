"""Spans of the serving path and kernel scopes of the compiled program.

The serving engine writes one host span per step of a micro-batch onto the
profiler's clock (``repro.serving.spans``), and the whole-model program
names every op after the model kernel it belongs to (``jax.named_scope`` in
``models.gnn.compile_model``).  Both are checked here on the CPU: the spans
in a recorded trace, the scopes in the program's HLO metadata.
"""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DynasparseEngine, SparseCOO
from repro.models import gnn
from repro.serving import ServingConfig, ServingEngine, SharedPlanCache

CHILDREN = ("serving.stack", "serving.drift", "serving.call",
            "serving.activation", "serving.wait")


def _graph(rng, n=80, nnz=240):
    flat = np.sort(rng.choice(n * n, size=nnz, replace=False))
    return SparseCOO((n, n), jnp.asarray((flat // n).astype(np.int32)),
                     jnp.asarray((flat % n).astype(np.int32)),
                     jnp.asarray(np.abs(rng.normal(size=nnz)
                                        ).astype(np.float32)),
                     tag="adjacency")


def _features(rng, n=80, d=12, block=8, block_density=0.35):
    """Block-sparse features, so the activation kernels take the block-skip
    route (and the batch pulls their telemetry)."""
    keep = rng.uniform(size=(-(-n // block), -(-d // block))) < block_density
    mask = np.kron(keep, np.ones((block, block)))[:n, :d]
    return (rng.normal(size=(n, d)) * mask).astype(np.float32)


def _spans(log_dir: str) -> list[tuple]:
    """``(name, start, end, thread, args)`` of every ``serving.*`` host
    event of the trace."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serving."):
                    out.append((e.name, e.start_ns, e.end_ns, line.name,
                                dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A GCN engine warmed with one eager and one compiled batch, then one
    compiled batch of four requests under a trace."""
    rng = np.random.default_rng(43)
    adj = _graph(rng)
    params = gnn.init_params("GCN", 12, 8, 5)
    eng = DynasparseEngine(tile_m=16, tile_n=8, literal=True,
                           cache=SharedPlanCache())
    srv = ServingEngine("GCN", params, engine=eng,
                        config=ServingConfig(max_batch=4))
    srv.register_graph("g", adj)
    pool = [_features(rng) for _ in range(4)]
    srv.serve(("g", h) for h in pool)            # eager: plans, compiles
    srv.serve(("g", h) for h in pool)            # compiled: warms shapes
    n0 = len(srv.stats.requests)
    log_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(log_dir):
        srv.serve(("g", h) for h in pool)
    srv.close()
    assert srv.stats.compiled_batches == 2 and srv.stats.batches == 3
    return _spans(log_dir), srv.stats.requests[n0:]


def test_every_span_is_written_with_its_ids(traced):
    spans, reqs = traced
    names = [s[0] for s in spans]
    assert names.count("serving.batch") == 1
    for child in CHILDREN + ("serving.split",):
        assert names.count(child) == 1, child
    enq = [s for s in spans if s[0] == "serving.enqueue"]
    assert sorted(s[4]["request"] for s in enq) == sorted(
        r.request_id for r in reqs)
    (batch,) = [s for s in spans if s[0] == "serving.batch"]
    args = batch[4]
    assert args["k"] == 4 and args["attempt"] == 0
    assert sorted(int(i) for i in str(args["requests"]).split()) == sorted(
        r.request_id for r in reqs)
    for s in spans:
        if s[0] in CHILDREN + ("serving.split",):
            assert s[4]["batch"] == args["batch"]


def test_children_nest_inside_the_batch_on_its_thread(traced):
    spans, _ = traced
    (batch,) = [s for s in spans if s[0] == "serving.batch"]
    children = sorted((s for s in spans if s[0] in CHILDREN),
                      key=lambda s: s[1])
    assert [s[0] for s in children] == list(CHILDREN)
    for name, start, end, thread, _ in children:
        assert batch[1] <= start <= end <= batch[2], name
        assert thread == batch[3], name
    # the hand-back follows the batch, whose span ends with its logits ready
    (split,) = [s for s in spans if s[0] == "serving.split"]
    assert batch[2] <= split[1] and split[3] == batch[3]


def test_batch_span_matches_the_request_stats(traced):
    spans, reqs = traced
    (batch,) = [s for s in spans if s[0] == "serving.batch"]
    assert {r.t_execute for r in reqs} == {reqs[0].t_execute}
    assert (batch[2] - batch[1]) * 1e-9 == pytest.approx(
        reqs[0].t_execute, abs=1e-3)


def test_replan_span_wraps_the_eager_pass(tmp_path):
    rng = np.random.default_rng(47)
    adj = _graph(rng)
    params = gnn.init_params("GCN", 12, 8, 5)
    eng = DynasparseEngine(tile_m=16, tile_n=8, literal=True,
                           cache=SharedPlanCache())
    with ServingEngine("GCN", params, engine=eng,
                       config=ServingConfig(max_batch=2)) as srv:
        srv.register_graph("g", adj)
        with jax.profiler.trace(str(tmp_path)):
            srv.serve(("g", _features(rng)) for _ in range(2))
    spans = _spans(str(tmp_path))
    names = [s[0] for s in spans]
    assert names.count("serving.replan") == 1
    assert "serving.call" not in names and "serving.drift" not in names
    (batch,) = [s for s in spans if s[0] == "serving.batch"]
    (replan,) = [s for s in spans if s[0] == "serving.replan"]
    assert batch[1] <= replan[1] <= replan[2] <= batch[2]


def _op_names(model: str) -> set[str]:
    """Every op name of the lowered whole-model program of ``model`` as
    served (stacked over two requests): the name locations that become the
    ``op_name`` of the compiled program's HLO metadata."""
    from repro.serving.engine import stacked_transport
    rng = np.random.default_rng(53)
    adj = _graph(rng)
    params = gnn.init_params(model, 12, 8, 5)
    eng = DynasparseEngine(tile_m=16, tile_n=8, literal=True)
    h = jnp.asarray(np.concatenate([_features(rng)] * 2, axis=1))
    _, cm = gnn.compile_model(model, eng, adj, h, params,
                              transport=stacked_transport)
    assert cm is not None
    text = cm.run.lower(cm.payload, h).as_text(debug_info=True)
    return set(re.findall(r'loc\("(jit\(replay\)/[^"]*)"', text))


KERNELS = {"GCN": ("l1-update", "l1-agg", "l2-update", "l2-agg"),
           "GIN": ("l1-agg", "l1-mlp1", "l1-mlp2", "l2-agg", "l2-mlp1",
                   "l2-mlp2")}


@pytest.mark.parametrize("model", sorted(KERNELS))
def test_every_kernel_is_a_scope_of_the_program(model):
    names = _op_names(model)
    scopes = {part for n in names for part in n.split("/")}
    for kernel in KERNELS[model]:
        assert kernel in scopes, (kernel, sorted(scopes))
    # the activation route's steps are scopes of their own under the kernel
    assert any(re.search(r"/l\d-(update|mlp\d)/pack/", n) for n in names)
    assert any(re.search(r"/(skip|dense)/", n) for n in names)
