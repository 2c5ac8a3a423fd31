"""From a profiler trace to the device's busy time, op times and idle gaps.

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain dict of events (all times in nanoseconds on the trace's clock):

- ``ops``: ``[name, start, dur]`` of every operation on the first device's
  ``XLA Ops`` line: what ran on the chip.  An op is named by its HLO
  instruction without the numbers XLA appends (``spdmm_fused.17`` and
  ``spdmm_fused.25`` are both ``spdmm_fused``), except a fusion, which keeps
  them.  A nested op (the body of a ``conditional``) is an event of its own
  inside its parent's;
- ``modules``: ``[name, start, dur]`` of every program execution on the same
  device (``XLA Modules``);
- ``host``: ``[name, start, dur, thread]`` of host events, the harness's own
  ``TraceAnnotation`` spans among them.

``reduce`` then works on that dict alone, so the arithmetic is tested on a
small recorded fixture without a chip.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.window"          # the harness's span around the window


def op_name(hlo: str) -> str:
    """``%spdmm_fused.17 = f32[...] custom-call(...)`` -> ``spdmm_fused``."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    base = re.sub(r"(\.(\d+|clone))+$", "", name)
    return name if base == "fusion" else base


def read_xplane(log_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = {"ops": [], "modules": [], "host": []}
    device = None
    for plane in pd.planes:
        if plane.name.startswith("/device:") and device is None:
            device = plane.name
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key == "ops":
                    out[key] += [[op_name(e.name), e.start_ns, e.duration_ns]
                                 for e in line.events]
                elif key:
                    out[key] += [[e.name, e.start_ns, e.duration_ns]
                                 for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [[e.name, e.start_ns, e.duration_ns, line.name]
                                for e in line.events]
    out["device"] = device
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` pairs into disjoint sorted intervals."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(intervals, spans) -> float:
    """Length of ``intervals`` (disjoint) that lies inside ``spans`` (disjoint)."""
    total, j = 0.0, 0
    spans = list(spans)
    for s, e in intervals:
        while j < len(spans) and spans[j][1] <= s:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < e:
            total += min(e, spans[k][1]) - max(s, spans[k][0])
            k += 1
    return total


def window_span(events: dict) -> tuple[float, float]:
    spans = [(s, s + d) for name, s, d, _ in events["host"] if name == WINDOW]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    return spans[-1]


def busy(events: dict, lo: float, hi: float) -> list[tuple[float, float]]:
    """Disjoint intervals in ``[lo, hi]`` in which an op ran on the device."""
    return union(clip([(s, s + d) for _, s, d in events["ops"]], lo, hi))


def program_busy(events: dict, lo: float, hi: float,
                 program: str) -> float:
    """Nanoseconds in ``[lo, hi]`` in which an op of a program whose module
    name starts with ``program`` ran."""
    mods = union(clip([(s, s + d) for n, s, d in events["modules"]
                       if n.startswith(program)], lo, hi))
    return covered(busy(events, lo, hi), mods)


def op_seconds(events: dict, lo: float, hi: float,
               top: int = 10) -> list[list]:
    """The ``top`` op names by device time inside ``[lo, hi]``; a nested op
    counts in its parent's time too."""
    tot: dict[str, float] = {}
    for name, s, d in events["ops"]:
        for a, b in clip([(s, s + d)], lo, hi):
            tot[name] = tot.get(name, 0.0) + (b - a)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns * 1e-9] for name, ns in ranked]


def idle_gaps(events: dict, lo: float, hi: float, top: int = 10) -> list[list]:
    """The ``top`` longest device-idle gaps in ``[lo, hi]``, each labelled by
    the longest host event that overlaps it most (what the host was doing),
    the window span itself left out."""
    gaps, t = [], lo
    for s, e in busy(events, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    host = [(n, s, s + d) for n, s, d, _ in events["host"] if n != WINDOW]
    out = []
    for a, b in gaps:
        best, label = 0.0, "no host event"
        for n, s, e in host:
            o = min(b, e) - max(a, s)
            if o > best:
                best, label = o, n
        out.append([label, (b - a) * 1e-9])
    return out
