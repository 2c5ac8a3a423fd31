"""Plain GCN (Kipf & Welling, arXiv:1609.02907, eq. 9), two layers.

``logits = Â · relu(Â · H · W1) · W2`` with ``Â = D^-1/2 (A + I) D^-1/2``
and no bias, as the served model has none.  ``forward`` is written once
against an aggregation ``agg(X) = Â · X``, a matmul ``dot`` and ``relu``, so
that the float64 reference and the lower-precision control share it.
"""
from costs import agg, dense

MODEL = "GCN"                     # the program's name for the architecture


def weight_shapes(cfg: dict) -> dict:
    d = cfg["dataset"]
    return {"W1": (d["features"], cfg["hidden"]),
            "W2": (cfg["hidden"], d["classes"])}


def forward(agg_, dot, relu, h, w):
    z = relu(agg_(dot(h, w["W1"])))
    return agg_(dot(z, w["W2"]))


def kernels(cfg: dict, nnz: int, batch: int) -> list:
    """The kernels of one micro-batch of ``batch`` requests, as served:
    each layer transforms first, since its fan-in exceeds its fan-out."""
    d, hid = cfg["dataset"], cfg["hidden"]
    n, f, c = d["vertices"], d["features"], d["classes"]
    return [dense("l1-update", batch * n, f, hid),
            agg("l1-agg", n, nnz, batch * hid),
            dense("l2-update", batch * n, hid, c),
            agg("l2-agg", n, nnz, batch * c)]
