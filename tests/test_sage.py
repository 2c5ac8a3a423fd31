"""GraphSAGE as served: each transform-first layer is ONE transform
``h·[W_self | W_neigh]`` whose neighbour half is aggregated.

The fused model is held to ``gnn.run_reference`` and to the textbook form
with two separate transforms, ``h·W_self + Â·(h·W_neigh)``, in both
association orders, directly and through ``ServingEngine`` micro-batched on
the eager and on the compiled path.  A zeroed ``W_self`` has to fail the
same comparison, so the root path is compared too.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DynasparseEngine, SparseCOO
from repro.models import gnn
from repro.serving import ServingConfig, ServingEngine, SharedPlanCache

# float32 products at HIGHEST, summed in another order (block by block in
# the kernels, whole rows in jnp.dot): a few float32 ulps of the largest
# logit.  Dropping the root term moves the logits by O(1) of that scale.
TOL = 1e-5

# (in, hidden, out): both layers transform first (fan-in >= fan-out), or
# both aggregate first (fan-in < fan-out, the root transform stays apart)
ORDERS = {"transform-first": (12, 8, 5), "aggregate-first": (4, 8, 12)}


def _graph(n=64, nnz=200, seed=5):
    rng = np.random.default_rng(seed)
    flat = np.sort(rng.choice(n * n, size=nnz, replace=False))
    return SparseCOO((n, n),
                     jnp.asarray((flat // n).astype(np.int32)),
                     jnp.asarray((flat % n).astype(np.int32)),
                     jnp.asarray(np.abs(rng.normal(size=nnz)
                                        ).astype(np.float32)),
                     tag="adjacency")


def _features(n, d, count, seed=11):
    rng = np.random.default_rng(seed)
    return [rng.normal(size=(n, d)).astype(np.float32) for _ in range(count)]


def _textbook(adj, h, p):
    """Two separate transforms per layer, float64 NumPy."""
    a = np.asarray(adj.todense(), np.float64)
    w = {k: np.asarray(v, np.float64) for k, v in p.items()}
    h = np.asarray(h, np.float64)
    z = np.maximum(h @ w["Ws1"] + a @ (h @ w["Wn1"]), 0.0)
    return z @ w["Ws2"] + a @ (z @ w["Wn2"])


def _rel_err(got, want) -> float:
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(np.asarray(got, np.float64) - want))
                 / np.max(np.abs(want)))


def _serving(params, *, compiled: bool, cache=None):
    eng = DynasparseEngine(tile_m=16, tile_n=8, literal=True,
                           cache=cache if cache is not None
                           else SharedPlanCache())
    return ServingEngine("GraphSAGE", params, engine=eng,
                         config=ServingConfig(max_batch=4,
                                              compile_models=compiled))


@pytest.mark.parametrize("order", list(ORDERS))
def test_reference_matches_the_textbook_form(order):
    d_in, hid, d_out = ORDERS[order]
    adj = _graph()
    params = gnn.init_params("GraphSAGE", d_in, hid, d_out, seed=3)
    for h in _features(64, d_in, 2):
        ref = gnn.run_reference("GraphSAGE", adj, jnp.asarray(h), params)
        assert _rel_err(ref, _textbook(adj, h, params)) <= TOL


@pytest.mark.parametrize("order", list(ORDERS))
def test_kernel_names_follow_the_order(order):
    d_in, hid, d_out = ORDERS[order]
    params = gnn.init_params("GraphSAGE", d_in, hid, d_out, seed=3)
    names = []

    def mm(x, y, name="kernel"):
        names.append(name)
        return gnn.reference_mm(x, y)

    gnn.sage_apply(mm, _graph(), jnp.asarray(_features(64, d_in, 1)[0]),
                   params)
    if order == "transform-first":
        assert names == ["l1-update", "l1-agg", "l2-update", "l2-agg"]
    else:
        assert names == ["l1-self", "l1-agg", "l1-update",
                         "l2-self", "l2-agg", "l2-update"]


@pytest.mark.parametrize("compiled", [False, True], ids=["eager", "compiled"])
@pytest.mark.parametrize("order", list(ORDERS))
def test_served_micro_batches_match_both_forms(order, compiled):
    d_in, hid, d_out = ORDERS[order]
    adj = _graph(seed=17)
    params = gnn.init_params("GraphSAGE", d_in, hid, d_out, seed=4)
    batches = _features(64, d_in, 10, seed=23)
    with _serving(params, compiled=compiled) as srv:
        srv.register_graph("g", adj)
        outs = srv.serve(("g", h) for h in batches)
        assert srv.stats.batches < len(batches)          # coalesced
        assert srv.stats.compiled_batches == (
            srv.stats.batches - 1 if compiled else 0)
    for h, z in zip(batches, outs):
        ref = gnn.run_reference("GraphSAGE", adj, jnp.asarray(h), params)
        assert _rel_err(z, ref) <= TOL
        assert _rel_err(z, _textbook(adj, h, params)) <= TOL


def test_compiled_program_has_one_update_kernel_per_layer():
    d_in, hid, d_out = ORDERS["transform-first"]
    adj = _graph(seed=19)
    params = gnn.init_params("GraphSAGE", d_in, hid, d_out, seed=5)
    batches = _features(64, d_in, 8, seed=29)
    with _serving(params, compiled=True) as srv:
        srv.register_graph("g", adj)
        srv.serve(("g", h) for h in batches)
        (cm,) = srv._compiled.values()
    assert [name for name, _ in cm.report.kernels] == [
        "l1-update", "l1-agg", "l2-update", "l2-agg"]
    h = jnp.concatenate([jnp.asarray(b) for b in batches[:4]], axis=1)
    hlo = cm.run.lower(cm.payload, h).as_text(debug_info=True)
    for layer in ("l1", "l2"):
        assert f"{layer}-update" in hlo and f"{layer}-agg" in hlo
        assert f"{layer}-self" not in hlo


def test_zero_root_weight_fails_the_comparison():
    d_in, hid, d_out = ORDERS["transform-first"]
    adj = _graph(seed=21)
    params = gnn.init_params("GraphSAGE", d_in, hid, d_out, seed=6)
    broken = dict(params, Ws1=jnp.zeros_like(params["Ws1"]))
    batches = _features(64, d_in, 8, seed=31)
    with _serving(broken, compiled=True) as srv:
        srv.register_graph("g", adj)
        outs = srv.serve(("g", h) for h in batches)
    for h, z in zip(batches, outs):
        assert _rel_err(z, gnn.run_reference(
            "GraphSAGE", adj, jnp.asarray(h), params)) > TOL
        assert _rel_err(z, _textbook(adj, h, params)) > TOL


def test_reference_runs_at_highest_precision(monkeypatch):
    seen = []
    real_dot = jnp.dot

    def dot(*args, **kw):
        seen.append(jax.config.jax_default_matmul_precision)
        return real_dot(*args, **kw)

    monkeypatch.setattr(jnp, "dot", dot)
    params = gnn.init_params("GraphSAGE", 12, 8, 5)
    gnn.run_reference("GraphSAGE", _graph(), jnp.ones((64, 12)), params)
    assert seen and set(seen) == {"highest"}
