"""Plain GIN (Xu et al., arXiv:1810.00826, eq. 4.1) with epsilon = 0, two
layers, each with a two-layer MLP, as the served model defines it:

    z1 = relu(relu((H + Â · H) · M1a) · M1b)
    logits = relu((z1 + Â · z1) · M2a) · M2b

The neighbour sum is taken over the renormalised adjacency with self-loops
``Â = D^-1/2 (A + I) D^-1/2`` that the served model uses, not the raw sum of
the paper; the last MLP has no activation after its output layer, and there
are no biases.  Aggregation is pinned to the raw features.  ``forward`` is
written once against ``agg(X) = Â · X``, ``dot`` and ``relu``.
"""
from costs import agg, dense

MODEL = "GIN"


def weight_shapes(cfg: dict) -> dict:
    d, hid = cfg["dataset"], cfg["hidden"]
    return {"M1a": (d["features"], hid), "M1b": (hid, hid),
            "M2a": (hid, hid), "M2b": (hid, d["classes"])}


def forward(agg_, dot, relu, h, w):
    z = h + agg_(h)
    z = relu(dot(relu(dot(z, w["M1a"])), w["M1b"]))
    z = z + agg_(z)
    return dot(relu(dot(z, w["M2a"])), w["M2b"])


def kernels(cfg: dict, nnz: int, batch: int) -> list:
    d, hid = cfg["dataset"], cfg["hidden"]
    n, f, c = d["vertices"], d["features"], d["classes"]
    return [agg("l1-agg", n, nnz, batch * f),
            dense("l1-mlp1", batch * n, f, hid),
            dense("l1-mlp2", batch * n, hid, hid),
            agg("l2-agg", n, nnz, batch * hid),
            dense("l2-mlp1", batch * n, hid, hid),
            dense("l2-mlp2", batch * n, hid, c)]
