"""The program's own spans and name scopes on a traced window.

Where the served program writes host spans (``serving.*``, made by
``repro.serving.spans.span``) and puts each model kernel under a
``jax.named_scope`` of its name, a traced window can say which step of the
program the device's idle time and busy time belong to.  This module reads
both from what ``devtrace.read_xplane`` already gives, and from the trace
file itself where that is not enough; a program that writes neither leaves
every reading here ``None``.

- The spans are host events of ``events["host"]`` (``[name, start, dur,
  thread]``), the harness's ``bench.window`` among them.
- A TPU v5e trace keeps an op's scope in the ``tf_op`` stat of the op's
  event metadata (``jit(replay)/l1-update/pack/scatter:``; a fusion of
  several ops lists their paths, ``;``-separated); an op without one (a
  ``conditional``) has it in the ``op_name`` metadata of its instruction in
  the program's HLO, which the trace keeps too.  ``ProfileData`` shows
  neither, so ``read_op_scopes`` decodes the ``.xplane.pb`` (protobuf wire
  format, the few fields it needs) and gives each op of ``events["ops"]``
  its paths, in the same order.

The reduction works on the events dict alone (``events["op_scopes"]`` where
the scopes are known), so the arithmetic is tested on hand-made and recorded
events without a chip.
"""
from __future__ import annotations

import argparse
import glob
import os
import re
import sys

import costs
import devtrace as tr

SPAN = "serving."                # prefix of the program's own host spans
BATCH = "serving.batch"          # one micro-batch, dispatch to logits ready
# the steps of a kernel that have a name scope of their own
# (repro.core.dispatch): the dense operand's layout, the activation's pack,
# and the two routes of the activation kernel
STEPS = ("layout", "pack", "skip", "dense")
TF_OP = "tf_op"                  # the stat that holds an op's scope path
HLO_PROTO = "Hlo Proto"          # the stat that holds a program's HLO


# ------------------------------------------------------- the trace file
def _varint(b, i: int) -> tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return r, i


def _fields(b):
    """``(field number, value)`` of each field of one protobuf message: an
    int for a varint, a memoryview for anything else."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _first(msg, field: int, default=None):
    return next((v for f, v in _fields(msg) if f == field), default)


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map(msg, field: int) -> dict:
    """A ``map<int64, message>`` field: key -> the value's bytes."""
    out = {}
    for f, entry in _fields(msg):
        if f == field:
            kv = dict(_fields(entry))
            out[kv.get(1, 0)] = kv.get(2, b"")
    return out


def _stats(meta, stat_names: dict) -> dict:
    """The stats of an ``XEventMetadata`` by name: a string (``str_value``,
    or ``ref_value``, a string interned as a stat metadata's name), a
    number, or bytes."""
    out = {}
    for f, stat in _fields(meta):
        if f == 5:
            s = dict(_fields(stat))
            name = stat_names.get(s.get(1))
            if 5 in s:
                out[name] = _text(s[5])
            elif 7 in s:
                out[name] = stat_names.get(s[7], "")
            else:
                out[name] = next((s[k] for k in (3, 4, 6) if k in s), None)
    return out


def _hlo_op_names(module) -> dict[str, str]:
    """Instruction name -> ``op_name`` of its metadata, from a serialized
    ``HloModuleProto``: computations (3); ``HloComputationProto``
    instructions (2); ``HloInstructionProto`` name (1), metadata (7);
    ``OpMetadata`` op_name (2)."""
    out = {}
    for f, comp in _fields(module):
        if f == 3:
            for g, ins in _fields(comp):
                if g == 2:
                    meta = _first(ins, 7)
                    if meta is not None:
                        out[_text(_first(ins, 1, b""))] = _text(
                            _first(meta, 2, b""))
    return out


def device_op_scopes(data: bytes) -> list[tuple[str, str]]:
    """``(event metadata name, scope paths)`` of each event of the first
    device plane's ``XLA Ops`` line of a serialized ``XSpace``, in the
    trace's order (the order ``read_xplane`` lists ``ops`` in).  The paths
    are the op's ``tf_op`` stat, or where it has none (a ``conditional``),
    the ``op_name`` of its instruction in its program's HLO, which the
    ``/host:metadata`` plane keeps (``Hlo Proto``, one per program);
    ``""`` where neither names one.

    Fields read: ``XSpace.planes`` (1); ``XPlane`` name (2), lines (3),
    event_metadata (4), stat_metadata (5); ``XLine`` name (2), events (4);
    ``XEvent`` metadata_id (1); ``XEventMetadata`` name (2), stats (5);
    ``XStatMetadata`` name (2); ``XStat`` metadata_id (1), uint64 (3),
    int64 (4), str (5), bytes (6), ref (7); ``HloProto`` hlo_module (1)."""
    device = hlo = None
    for f, plane in _fields(memoryview(data)):
        name = _text(_first(plane, 2, b"")) if f == 1 else ""
        if name.startswith("/device:") and device is None:
            device = plane
        elif name == "/host:metadata":
            hlo = plane
    if device is None:
        return []
    programs = {}                # program id -> serialized HloModuleProto
    if hlo is not None:
        names = {k: _text(_first(v, 2, b""))
                 for k, v in _map(hlo, 5).items()}
        for meta in _map(hlo, 4).values():
            pid = re.search(r"\((\d+)\)$", _text(_first(meta, 2, b"")))
            proto = _stats(meta, names).get(HLO_PROTO)
            if pid and isinstance(proto, memoryview):
                programs[int(pid.group(1))] = _first(proto, 1, b"")
    op_names: dict[int, dict] = {}
    stat_names = {k: _text(_first(v, 2, b""))
                  for k, v in _map(device, 5).items()}
    meta = {}
    for k, v in _map(device, 4).items():
        name = _text(_first(v, 2, b""))
        stats = _stats(v, stat_names)
        scope = paths(stats.get(TF_OP) or "")
        pid = stats.get("program_id")
        if not scope and pid in programs:
            if pid not in op_names:
                op_names[pid] = _hlo_op_names(programs[pid])
            scope = op_names[pid].get(name.split(" = ", 1)[0].lstrip("%"),
                                      "")
        meta[k] = (name, scope)
    out = []
    for g, line in _fields(device):
        if g == 3 and _text(_first(line, 2, b"")) == "XLA Ops":
            out += [meta.get(_first(e, 1, 0), ("", ""))
                    for h, e in _fields(line) if h == 4]
    return out


def paths(tf_op: str) -> str:
    """``a/b:type`` or ``a/b;a/c:`` -> ``a/b`` or ``a/b;a/c``: the scope
    paths of an op, its op type left out."""
    return tf_op.rpartition(":")[0] if ":" in tf_op else tf_op


def read_op_scopes(log_dir: str, ops: list) -> list[str] | None:
    """The scope paths of each op of ``ops`` (``read_xplane(log_dir)["ops"]``)
    from the same trace file, or ``None`` where that file is not there or
    lists other ops (another run's trace)."""
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        return None
    with open(files[-1], "rb") as f:
        data = f.read()
    try:
        found = device_op_scopes(data)
    except (ValueError, IndexError) as e:    # not a trace this can decode
        print(f"[bench] no op scopes read from {files[-1]}: {e!r}",
              file=sys.stderr, flush=True)
        return None
    if len(found) != len(ops) or any(
            tr.op_name(name) != op[0] for (name, _), op in zip(found, ops)):
        return None
    return [scope for _, scope in found]


# ------------------------------------------------------- the reduction
def under(path: str, scope: str) -> bool:
    """Is ``scope`` (``l1-update`` or ``l1-update/pack``) a scope of an op
    whose paths are ``path`` (``;``-separated): the scope's parts, in order,
    among the parts of one of them?"""
    for one in path.split(";"):
        parts = iter(one.split("/"))
        if all(any(p == q for p in parts) for q in scope.split("/")):
            return True
    return False


def scope_busy(events: dict, lo: float, hi: float, scope,
               program: str) -> float:
    """Nanoseconds in ``[lo, hi]`` in which an op under ``scope`` (or under
    any of a list of scopes) of a program whose module name starts with
    ``program`` ran: the union of those ops' intervals, so a nested op is
    not counted twice."""
    scopes = [scope] if isinstance(scope, str) else list(scope)
    mods = tr.union(tr.clip([(s, s + d) for n, s, d in events["modules"]
                             if n.startswith(program)], lo, hi))
    ops = tr.union(tr.clip([(s, s + d) for (_, s, d), path in zip(
        events["ops"], events["op_scopes"])
        if any(under(path, sc) for sc in scopes)], lo, hi))
    return tr.covered(ops, mods)


def op_scope_seconds(events: dict, lo: float, hi: float,
                     top: int = 10) -> list[list]:
    """The ``top`` pairs of op name and scope paths by device time inside
    ``[lo, hi]``, as ``devtrace.op_seconds`` counts it."""
    tot: dict[tuple, float] = {}
    for (name, s, d), path in zip(events["ops"], events["op_scopes"]):
        for a, b in tr.clip([(s, s + d)], lo, hi):
            tot[name, path] = tot.get((name, path), 0.0) + (b - a)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[name, path, ns * 1e-9] for (name, path), ns in ranked]


def idle(events: dict, lo: float, hi: float) -> list[tuple[float, float]]:
    """Disjoint intervals in ``[lo, hi]`` in which no op ran on the device."""
    gaps, t = [], lo
    for s, e in tr.busy(events, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def span_intervals(events: dict, name: str) -> list[tuple[float, float]]:
    """Disjoint intervals in which a host span ``name`` was open."""
    return tr.union((s, s + d) for n, s, d, _ in events["host"] if n == name)


def idle_by_span(events: dict, lo: float, hi: float) -> dict[str, float]:
    """Device-idle nanoseconds in ``[lo, hi]`` under each program span, each
    moment put down to the innermost span open then (the one opened last,
    whatever its thread), and under ``"none"`` where no span was open."""
    edges = []                   # (time, opens, (-start, dur, name))
    for name, s, d, _ in events["host"]:
        if name.startswith(SPAN):
            for a, b in tr.clip([(s, s + d)], lo, hi):
                edges += [(a, 1, (-s, d, name)), (b, 0, (-s, d, name))]
    edges.sort(key=lambda e: e[:2])          # a span closes before one opens
    gaps = idle(events, lo, hi)
    out: dict[str, float] = {}
    open_: list = []
    t, g = lo, 0
    for at, opens, key in edges + [(hi, 0, None)]:
        if at > t:
            # no span opens or closes in [t, at): one label for its idle part
            label = min(open_)[2] if open_ else "none"
            while g < len(gaps) and gaps[g][1] <= t:
                g += 1
            for a, b in gaps[g:]:
                if a >= at:
                    break
                out[label] = out.get(label, 0.0) + min(b, at) - max(a, t)
            t = at
        if key is not None and opens:
            open_.append(key)
        elif key is not None:
            open_.remove(key)
    return out


# ------------------------------------------------------- one run
def trace_dir(run) -> str:
    """Where the run's profiler wrote: ``--trace-dir`` where the command
    gave one, else the harness's default for the cell."""
    import harness
    # no abbreviations: ``--trace 1`` is not ``--trace-dir 1``
    ap = argparse.ArgumentParser(add_help=False, allow_abbrev=False)
    ap.add_argument("--trace-dir", default=None)
    given = ap.parse_known_args(sys.argv[1:])[0].trace_dir
    return given or str(harness.STATE / "trace" / run.cell)


def op_scopes(run) -> list[str] | None:
    """``run.events["op_scopes"]``, read from the run's trace file the first
    time it is asked for; ``None`` where no op has a scope."""
    ev = run.events
    if "op_scopes" not in ev:
        found = read_op_scopes(trace_dir(run), ev["ops"])
        ev["op_scopes"] = found if found and any(found) else None
    return ev["op_scopes"]


def least_s(run, name: str) -> float:
    """Least time of kernel ``name`` in the window's batches, each batch
    weighted by the share of it inside the window (as
    ``Run.kernel_roofline`` weighs all kernels)."""
    t_lo, t_hi = run.edges["t_start"], run.edges["t_end"]
    least = 0.0
    for t0, t1, k in run.batches():
        inside = min(t1, t_hi) - max(t0, t_lo)
        if inside > 0:
            ks = [x for x in run.kernels(k) if x.name == name]
            least += costs.least_s(ks, run.peak) * (inside / (t1 - t0))
    return least


def scope_roofline(run, kernel: str) -> float | None:
    """Least time of ``kernel`` in the window's batches over the device time
    of the served program's ops under its name scope, in %; nothing to read
    where the trace names no scopes."""
    if run.events is None or not run.peak or op_scopes(run) is None:
        return None
    report(run)
    lo, hi = run.window_ns()
    device = scope_busy(run.events, lo, hi, kernel, run.program)
    if device <= 0:
        return None
    return 100.0 * least_s(run, kernel) * 1e9 / device


def idle_in_batches_ns(run) -> float | None:
    """Device-idle nanoseconds of the window while a micro-batch's span
    (``serving.batch``) was open; nothing to read where the program writes
    no such span."""
    if run.events is None:
        return None
    batches = span_intervals(run.events, BATCH)
    if not batches:
        return None
    report(run)
    lo, hi = run.window_ns()
    return tr.covered(idle(run.events, lo, hi), tr.clip(batches, lo, hi))


def report(run) -> None:
    """Log, once per run: device-idle seconds under each program span, and
    where the ops have scopes, device seconds under each model kernel's
    scope and its steps, the share of the served program's time under no
    kernel scope, and the scope of each of the top ops."""
    import harness
    ev = run.events
    if ev.get("spans_reported"):
        return
    ev["spans_reported"] = True
    lo, hi = run.window_ns()
    for name, ns in sorted(idle_by_span(ev, lo, hi).items(),
                           key=lambda kv: -kv[1]):
        harness.log(f"device idle under {name}: {ns * 1e-9:.6f} s")
    if op_scopes(run) is None:
        return
    names = [k.name for k in run.kernels(1)]
    for name in names:
        for scope in [name] + [f"{name}/{sub}" for sub in STEPS]:
            ns = scope_busy(ev, lo, hi, scope, run.program)
            if ns > 0:
                harness.log(f"device busy under {scope}: {ns * 1e-9:.6f} s")
    program = tr.program_busy(ev, lo, hi, run.program)
    none = program - scope_busy(ev, lo, hi, names, run.program)
    harness.log(f"device time of {run.program} under no model-kernel scope: "
                f"{none * 1e-9:.6f} s of {program * 1e-9:.6f} s "
                f"({100.0 * none / max(program, 1.0):.3f}%)")
    for name, path, sec in op_scope_seconds(ev, lo, hi):
        harness.log(f"op {name} under {path or 'no scope'}: {sec:.6f} s")
