"""The one traffic generator: reads a mix's parameters from ``traffic/<mix>.json``.

A mix's keys:

- ``loop``: ``"closed"``: ``clients`` callers, each sending its next request
  when the last one's logits are on the host;
- ``features``: what a request carries, ``"bow-noise"`` (the dataset's
  bag-of-words features with ``noise`` added to their non-zeros, so the
  sparsity pattern stays put) or ``"gaussian"`` (dense standard normal
  features of the same width, as embeddings from an encoder would be);
- ``pool``: how many distinct feature matrices the run draws requests from;
- ``warm_batch_sizes``: the micro-batch sizes set-up serves once each, so
  that every shape the window meets is compiled before it opens.

Everything is drawn from the run's seed: the pool, and which client sends
which pool entry.  Every seed sends the same kind and number of feature
matrices, so seeds change the values and order of the requests and not the
amount of work.
"""
from __future__ import annotations

import asyncio
import dataclasses
import time

import numpy as np


@dataclasses.dataclass
class Record:
    """One request as its client saw it."""
    idx: int                  # pool entry sent
    t_due: float              # send time
    t_ready: float = 0.0      # the server handed the logits back
    t_done: float = 0.0       # logits held on the host
    logits: np.ndarray | None = None
    error: str | None = None


def make_pool(traffic: dict, features: np.ndarray,
              rng: np.random.Generator) -> list[np.ndarray]:
    kind, n = traffic["features"], traffic["pool"]
    if kind == "bow-noise":
        mask = features != 0
        nz = int(mask.sum())
        out = []
        for _ in range(n):
            h = features.copy()
            h[mask] += rng.normal(0.0, traffic["noise"], size=nz).astype(
                np.float32)
            out.append(h)
        return out
    if kind == "gaussian":
        return [rng.standard_normal(features.shape, dtype=np.float32)
                for _ in range(n)]
    raise ValueError(f"unknown request features {kind!r}")


def closed_order(pool: int, clients: int,
                 rng: np.random.Generator) -> np.ndarray:
    """``order[c]``: the pool entries client ``c`` sends, cycled."""
    perm = rng.permutation(pool)
    return np.stack([np.roll(perm, -c) for c in range(clients)])


async def _send(infer, rec: Record, h) -> None:
    try:
        z = await infer(h)
        rec.t_ready = time.perf_counter()
        rec.logits = np.asarray(z)
    except Exception as exc:          # recorded, judged by the check
        rec.error = f"{type(exc).__name__}: {exc}"
    rec.t_done = time.perf_counter()


async def run_window(infer, pool: list[np.ndarray], traffic: dict,
                     seconds: float, rng: np.random.Generator, *,
                     on_open=None, on_close=None,
                     drain_s: float = 60.0) -> tuple[list[Record], dict]:
    """Drive ``infer`` (an ``async (features) -> logits``) for ``seconds``.

    Returns every request sent in the window, and the window's edges on the
    host clock.  ``on_open`` is called as the window opens and ``on_close``
    as it closes.  Requests still in flight when the window closes are
    waited for up to ``drain_s``; one that never answers keeps no logits.
    """
    recs: list[Record] = []
    if on_open is not None:
        on_open()
    t0 = time.perf_counter()
    if on_close is not None:
        asyncio.get_running_loop().call_later(seconds, on_close)
    t_end = t0 + seconds
    if traffic["loop"] != "closed":
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    order = closed_order(len(pool), traffic["clients"], rng)

    async def client(c: int) -> None:
        j = 0
        while time.perf_counter() < t_end:
            rec = Record(idx=int(order[c][j % len(pool)]),
                         t_due=time.perf_counter())
            recs.append(rec)
            await _send(infer, rec, pool[rec.idx])
            j += 1

    tasks = [asyncio.ensure_future(client(c))
             for c in range(traffic["clients"])]
    rest = max(0.0, t_end - time.perf_counter())
    await asyncio.wait(tasks, timeout=rest + drain_s)
    for t in tasks:
        t.cancel()
    return recs, {"t_start": t0, "t_end": t_end}
