"""Plain GraphSAGE (Hamilton, Ying & Leskovec, arXiv:1706.02216, Alg. 1),
two layers, as the served model defines it:

    z1 = relu(H · Ws1 + Â · (H · Wn1))
    logits = z1 · Ws2 + Â · (z1 · Wn2)

which is the paper's ``W · CONCAT(h_v, AGG(h_N(v)))`` with ``W`` split into
its root half ``Ws`` and its neighbour half ``Wn``.  ``forward`` keeps the
two products of this textbook form apart, whatever the program fuses.  Its
departures from the paper are all the served model's:

- the aggregator is the renormalised adjacency ``Â = D^-1/2 (A + I) D^-1/2``
  that the harness builds for every graph, not the mean over neighbours;
- the whole neighbourhood, no sampling (full-graph inference, as the
  paper's accelerator does it);
- no biases;
- no L2 normalisation of each layer's output (Alg. 1, line 7).

``forward`` is written once against ``agg(X) = Â · X``, ``dot`` and ``relu``.
"""
from costs import agg, dense

MODEL = "GraphSAGE"


def weight_shapes(cfg: dict) -> dict:
    d, hid = cfg["dataset"], cfg["hidden"]
    f, c = d["features"], d["classes"]
    return {"Ws1": (f, hid), "Wn1": (f, hid),
            "Ws2": (hid, c), "Wn2": (hid, c)}


def forward(agg_, dot, relu, h, w):
    z = relu(dot(h, w["Ws1"]) + agg_(dot(h, w["Wn1"])))
    return dot(z, w["Ws2"]) + agg_(dot(z, w["Wn2"]))


def kernels(cfg: dict, nnz: int, batch: int) -> list:
    """The kernels of one micro-batch of ``batch`` requests, as served: each
    layer transforms first, root and neighbour weights side by side in one
    transform, and aggregates the neighbour half."""
    d, hid = cfg["dataset"], cfg["hidden"]
    n, f, c = d["vertices"], d["features"], d["classes"]
    return [dense("l1-update", batch * n, f, 2 * hid),
            agg("l1-agg", n, nnz, batch * hid),
            dense("l2-update", batch * n, hid, 2 * c),
            agg("l2-agg", n, nnz, batch * c)]
