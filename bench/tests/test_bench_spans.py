"""The program's spans and kernel scopes on a traced window (``spantrace``):
the reduction against hand sums on hand-made events, the decoding of op
scopes from a hand-encoded trace file, the four readers that use them, a
slice of a ``gcn-co.sat`` window recorded on a TPU v5e
(``trace_fixture_spans.json``), and a whole traced run on the CPU with and
without the program's spans."""
import contextlib
import json
import os
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import costs  # noqa: E402
import devtrace as tr  # noqa: E402
import harness  # noqa: E402
import spantrace as st  # noqa: E402
from test_bench_check import SPEC, _server, _tiny  # noqa: E402
from test_bench_metrics import PEAK, _read, _run  # noqa: E402
from test_bench_trace import EVENTS  # noqa: E402

# the hand-made trace of test_bench_trace with the program's spans and
# scopes: the device is idle in [15, 20) and [30, 40); a micro-batch is open
# in [12, 22) and [36, 45)
SCOPED = dict(
    EVENTS,
    op_scopes=["jit(replay)/l1-update/pack/scatter",
               "jit(replay)/l1-update/cond/skip/jit(spdmm_fused)/spdmm_fused"
               "/pallas_call",
               "jit(concatenate)/concatenate", "jit(replay)/l2-agg/copy"],
    host=EVENTS["host"] + [["serving.batch", 12, 10, "w"],
                           ["serving.wait", 13, 8, "w"],
                           ["serving.split", 22, 2, "w"],
                           ["serving.enqueue", 32, 4, "python"],
                           ["serving.batch", 36, 9, "w"]])
GCN = ("l1-update", "l1-agg", "l2-update", "l2-agg")


def test_scope_busy_is_the_union_under_the_scope_inside_the_program():
    assert st.scope_busy(SCOPED, 0, 40, "l1-update", "jit_replay") == 15
    assert st.scope_busy(SCOPED, 0, 40, "l1-update/pack", "jit_replay") == 10
    assert st.scope_busy(SCOPED, 0, 40, "l1-update/skip", "jit_replay") == 10
    assert st.scope_busy(SCOPED, 0, 40, "l2-agg", "jit_replay") == 0
    assert st.scope_busy(SCOPED, 0, 12, "l1-update", "jit_replay") == 12
    assert st.scope_busy(SCOPED, 0, 40, ["l1-update", "l2-agg"],
                         "jit_replay") == 15


@pytest.mark.parametrize("path,scope,want", [
    ("jit(replay)/l1-update/cond/skip/x", "l1-update/skip", True),
    ("jit(replay)/l1-update/cond/skip/x", "skip/l1-update", False),
    ("jit(replay)/l1-update/x", "l1", False),
    ("jit(replay)/l1-agg/x;jit(replay)/l2-update/pack/y", "l2-update/pack",
     True),
    ("", "l1-update", False)])
def test_a_scope_is_whole_parts_in_order_of_one_path(path, scope, want):
    assert st.under(path, scope) is want


def test_paths_leave_out_the_op_type():
    assert st.paths("jit(replay)/l1-agg/transpose;jit(replay)/l1-agg/"
                    "reshape:") == ("jit(replay)/l1-agg/transpose;"
                                    "jit(replay)/l1-agg/reshape")
    assert st.paths("a/b:Add") == "a/b"
    assert st.paths("") == ""


def test_idle_by_span_puts_each_idle_moment_to_the_innermost_span():
    assert st.idle(SCOPED, 0, 40) == [(15, 20), (30, 40)]
    by = st.idle_by_span(SCOPED, 0, 40)
    assert by == {"serving.wait": 5, "none": 2, "serving.enqueue": 4,
                  "serving.batch": 4}
    assert sum(by.values()) == 40 - 25
    assert st.idle_by_span(EVENTS, 0, 40) == {"none": 15}
    assert st.span_intervals(SCOPED, st.BATCH) == [(12, 22), (36, 45)]


def test_op_scope_seconds_ranks_ops_with_their_scope():
    # one name under two scopes is two entries
    assert st.op_scope_seconds(SCOPED, 0, 40) == [
        ["fusion.1", "jit(replay)/l1-update/pack/scatter", 10e-9],
        ["custom-call", "jit(replay)/l1-update/cond/skip/jit(spdmm_fused)"
         "/spdmm_fused/pallas_call", 10e-9],
        ["fusion.1", "jit(concatenate)/concatenate", 10e-9]]


# ------------------------------------------- a hand-encoded trace file
def _varint(n: int) -> bytes:
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        if not n:
            return out + bytes([b])
        out += bytes([b | 0x80])


def _msg(*fields) -> bytes:
    """A protobuf message of ``(field, value)`` pairs: an int is a varint,
    anything else length-delimited."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def _plane(name, lines, events=(), stats=()):
    """An ``XPlane``: ``lines`` of ``(name, t0_ns, [(metadata id, offset
    ns, dur ns)])``, event metadata ``(id, name, [XStat])``, stat metadata
    ``(id, name)``."""
    return _msg(
        (2, name),
        *[(3, _msg((1, i + 1), (2, ln), (3, t0), *[
            (4, _msg((1, m), (2, off * 1000), (3, dur * 1000)))
            for m, off, dur in evs]))
          for i, (ln, t0, evs) in enumerate(lines)],
        *[(4, _msg((1, i), (2, _msg((1, i), (2, n), *[(5, s) for s in ss]))))
          for i, n, ss in events],
        *[(5, _msg((1, i), (2, _msg((1, i), (2, n))))) for i, n in stats])


TF_OP, PROGRAM, HLO, INTERNED = 1, 2, 3, 4
OPS = [  # (metadata id, HLO instruction, stats)
    (10, "%cond.1 = f32[8] conditional(%p, %a, %b)", [_msg((1, PROGRAM),
                                                           (3, 77))]),
    (11, "%spdmm_fused.3 = f32[8] custom-call(%a)", [
        _msg((1, TF_OP), (5, "jit(replay)/l1-update/cond/branch_0_fun/skip"
                             "/spdmm_fused:")), _msg((1, PROGRAM), (3, 77))]),
    (12, "%fusion.7 = s32[8] fusion(%a)", [_msg((1, TF_OP), (7, INTERNED))]),
    (13, "%copy-start = (s32[8]) copy-start(%a)", [_msg((1, PROGRAM),
                                                        (3, 77))])]


def _xspace() -> bytes:
    module = _msg((1, "jit_replay"), (3, _msg((1, "main"), *[
        (2, _msg((1, name), (2, "x"), (7, _msg((2, op_name)))))
        for name, op_name in [("cond.1", "jit(replay)/l1-update/cond"),
                              ("copy.2", "jit(replay)/l1-agg/copy")]])))
    device = _plane(
        "/device:TPU:0",
        [("XLA Modules", 1000, [(20, 0, 100)]),
         ("XLA Ops", 1000, [(10, 10, 50), (11, 20, 30), (12, 70, 10),
                            (13, 85, 5)])],
        [(20, "jit_replay(77)", [])] + [(i, n, s) for i, n, s in OPS],
        [(TF_OP, "tf_op"), (PROGRAM, "program_id"),
         (INTERNED, "jit(replay)/l1-update/pack/scatter;"
                    "jit(replay)/l1-update/pack/reshape:")])
    hlo_proto = _msg((1, 1), (6, _msg((1, module))))
    meta = _plane("/host:metadata", [],
                  [(1, "jit_replay(77)", [hlo_proto])], [(1, "Hlo Proto")])
    host = _plane("/host:CPU", [("python3", 1000, [(1, 0, 200)])],
                  [(1, tr.WINDOW, [])])
    return _msg((1, meta), (1, device), (1, host))


def _write(tmp_path, data: bytes) -> str:
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(data)
    return str(tmp_path)


def test_op_scopes_come_from_tf_op_or_the_programs_hlo(tmp_path):
    log_dir = _write(tmp_path, _xspace())
    ev = tr.read_xplane(log_dir)
    assert [o[0] for o in ev["ops"]] == ["cond", "spdmm_fused", "fusion.7",
                                         "copy-start"]
    assert st.read_op_scopes(log_dir, ev["ops"]) == [
        "jit(replay)/l1-update/cond",
        "jit(replay)/l1-update/cond/branch_0_fun/skip/spdmm_fused",
        "jit(replay)/l1-update/pack/scatter;"
        "jit(replay)/l1-update/pack/reshape",
        ""]


def test_op_scopes_are_not_read_from_another_runs_trace(tmp_path):
    log_dir = _write(tmp_path, _xspace())
    ops = tr.read_xplane(log_dir)["ops"]
    assert st.read_op_scopes(log_dir, ops[:3]) is None
    assert st.read_op_scopes(log_dir, [["copy"]] + ops[1:]) is None
    assert st.read_op_scopes(str(tmp_path / "none"), ops) is None


def test_an_undecodable_trace_gives_no_op_scopes(tmp_path):
    log_dir = _write(tmp_path, b"\x0f\x01\x02")
    assert st.read_op_scopes(log_dir, []) is None


@pytest.mark.parametrize("argv,want", [
    (["--workload", "c", "--trace", "1"], None),
    (["--workload", "c", "--trace", "1", "--trace-dir", "/x/t"], "/x/t"),
    (["--trace-dir=/x/t", "--trace", "1"], "/x/t")])
def test_the_trace_is_read_where_the_run_wrote_it(argv, want, monkeypatch):
    monkeypatch.setattr(sys, "argv", ["bench/main.py"] + argv)
    assert st.trace_dir(_run()) == (
        want or str(harness.STATE / "trace" / "x"))


def test_read_xplane_keeps_the_program_spans(tmp_path):
    """On the CPU: a trace with the harness's window and a program span."""
    import jax
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            with jax.profiler.TraceAnnotation(
                    "serving.batch", batch=4, requests="7 8"):
                jax.block_until_ready(jax.numpy.ones(4) + 1)
    ev = tr.read_xplane(str(tmp_path))
    (span,) = [h for h in ev["host"] if h[0].startswith(st.SPAN)]
    name, start, dur, _ = span
    assert name == "serving.batch"
    lo, hi = tr.window_span(ev)
    assert lo <= start and start + dur <= hi
    assert st.span_intervals(ev, st.BATCH) == [(start, start + dur)]


# ------------------------------------------- a slice of a chip trace
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "trace_fixture_spans.json")


def test_recorded_trace_with_spans_and_scopes():
    """A 0.42 s slice of a ``gcn-co.sat`` window on a TPU v5e, with the
    program's spans among the host events and each op's scope paths as
    ``read_op_scopes`` decoded them from the trace file."""
    with open(FIXTURE) as f:
        ev = json.load(f)
    lo, hi = tr.window_span(ev)
    idle_ns = hi - lo - sum(e - s for s, e in tr.busy(ev, lo, hi))
    by = st.idle_by_span(ev, lo, hi)
    assert sum(by.values()) == pytest.approx(idle_ns)
    assert set(by) <= {"none"} | {h[0] for h in ev["host"]}
    batches = tr.clip(st.span_intervals(ev, st.BATCH), lo, hi)
    inside = tr.covered(st.idle(ev, lo, hi), batches)
    assert 0 < inside < idle_ns
    # each step of a batch lies inside a batch's span, on its thread
    open_ = [h for h in ev["host"] if h[0] == st.BATCH]
    steps = [h for h in ev["host"] if h[0] in (
        "serving.stack", "serving.drift", "serving.call",
        "serving.activation", "serving.wait")]
    assert steps
    for name, start, dur, thread in steps:
        assert any(b[1] <= start and start + dur <= b[1] + b[2]
                   and thread == b[3] for b in open_), name
    # the kernels' scopes do not overlap, and leave little of the program
    program = tr.program_busy(ev, lo, hi, "jit_replay")
    kernels = st.scope_busy(ev, lo, hi, GCN, "jit_replay")
    each = [st.scope_busy(ev, lo, hi, k, "jit_replay") for k in GCN]
    assert sum(each) == pytest.approx(kernels)
    assert 0 < program - kernels < 0.05 * program
    for k in ("l1-update/pack", "l1-update/skip"):
        assert 0 < st.scope_busy(ev, lo, hi, k, "jit_replay") < each[0]
    # the unnamed fusion of the breakdown is the pack's scatter, and the
    # conditional (no tf_op of its own) is put under its kernel from the HLO
    scopes = {n: {p for (m, _, _), p in zip(ev["ops"], ev["op_scopes"])
                  if m == n} for n in ("fusion.7", "cond")}
    assert scopes["fusion.7"] == {"jit(replay)/l1-update/pack/scatter"}
    assert scopes["cond"] <= {"jit(replay)/l1-update/cond",
                              "jit(replay)/l2-update/cond"}


# ------------------------------------------- the four readers
def _scoped_run(spans=True, scopes=True) -> harness.Run:
    """``_run`` with a trace whose op in batch A is under ``l1-update`` and
    whose op in batch B is under ``l1-agg``, and the program's batch spans
    over the host batches."""
    off = 1e9
    ns = lambda t: t * 1e9 + off  # noqa: E731
    events = {
        "ops": [["spdmm_fused", ns(101.5), 1e9], ["spmm_fused", ns(108.0),
                                                   1e9]],
        "modules": [["jit_replay(1)", ns(101.0), 2e9],
                    ["jit_replay(1)", ns(108.0), 2e9]],
        "host": [[tr.WINDOW, ns(100.0), 10e9, "python3"]]}
    if scopes:
        events["op_scopes"] = ["jit(replay)/l1-update/cond/skip/spdmm_fused",
                               "jit(replay)/l1-agg/spmm_fused"]
    if spans:
        events["host"] += [["serving.batch", ns(101.0), 2e9, "w"],
                           ["serving.batch", ns(108.0), 4e9, "w"]]
    run = _run(events)
    run.kernels = lambda k: [costs.dense("l1-update", k * 10, 100, 10),
                             costs.agg("l1-agg", 10, 30, k * 4)]
    return run


def test_idle_in_and_between_batches_sum_to_the_idle_share():
    run = _scoped_run()
    # idle inside the batches: [101, 101.5), [102.5, 103), [109, 110)
    assert _read("idle_in_batch.sat", run) == pytest.approx(20.0)
    assert _read("idle_between_batches.sat", run) == pytest.approx(60.0)
    assert (_read("idle_in_batch.sat", run)
            + _read("idle_between_batches.sat", run)) == pytest.approx(
        _read("idle_share.sat", run), abs=1e-9)


def test_kernel_rooflines_read_the_device_time_under_each_scope():
    run = _scoped_run()
    # batch A lies inside the window, batch B half inside; one second of
    # device time under each scope
    for name, metric in (("l1-update", "l1_update_roofline.sat"),
                         ("l1-agg", "l1_agg_roofline.sat")):
        (k,) = [x for x in run.kernels(2) if x.name == name]
        want = 100 * k.least_s(PEAK) * 1.5 / 1.0
        assert _read(metric, run) == pytest.approx(want)
    # the aggregate keeps its definition: every kernel over all device time
    least = sum(x.least_s(PEAK) for x in run.kernels(2)) * 1.5
    assert _read("kernel_roofline.sat", run) == pytest.approx(
        100 * least / 2.0)


@pytest.mark.parametrize("metric", [
    "idle_in_batch.sat", "idle_between_batches.sat",
    "l1_update_roofline.sat", "l1_agg_roofline.sat"])
def test_span_and_scope_metrics_are_silent_where_the_program_writes_none(
        metric):
    """A program without spans or scopes (as before they were added) gives
    nothing to read, and does not raise."""
    assert _read(metric, _run()) is None
    assert _read(metric, _scoped_run(spans=False, scopes=False)) is None


def test_scope_roofline_is_silent_for_a_kernel_with_no_ops():
    run = _scoped_run()
    run.events["op_scopes"] = ["jit(replay)/cond", "jit(replay)"]
    assert _read("l1_update_roofline.sat", run) is None
    assert _read("idle_in_batch.sat", run) == pytest.approx(20.0)


def test_the_run_logs_where_idle_and_busy_time_went_once(capsys):
    run = _scoped_run()
    _read("idle_in_batch.sat", run)
    _read("l1_agg_roofline.sat", run)
    err = capsys.readouterr().err
    assert "device idle under none: 6.000000 s" in err
    assert "device idle under serving.batch: 2.000000 s" in err
    assert "device busy under l1-update: 1.000000 s" in err
    assert "device busy under l1-update/skip: 1.000000 s" in err
    assert ("device time of jit_replay under no model-kernel scope: "
            "0.000000 s of 2.000000 s (0.000%)") in err
    assert err.count("device idle under none") == 1
    # without scopes, only the idle time by span
    run = _scoped_run(scopes=False)
    _read("idle_in_batch.sat", run)
    err = capsys.readouterr().err
    assert "device idle under none" in err and "device busy" not in err


# ------------------------------------------- a whole traced run
@pytest.mark.parametrize("spans", [True, False],
                         ids=["program-spans", "no-program-spans"])
def test_traced_run_reports_the_span_metrics(spans, monkeypatch, tmp_path):
    """A traced run on the CPU (no device ops: the device reads idle
    throughout), its trace in the harness's default place, reports the idle
    split by the program's batch spans, and where the program writes no
    spans (as before they were added) leaves those metrics out without
    failing."""
    from repro.serving import engine
    if not spans:
        monkeypatch.setattr(engine, "span",
                            lambda name, **ids: contextlib.nullcontext())
    monkeypatch.setattr(harness, "STATE", tmp_path)
    traffic = dict(harness.load_traffic("sat"), clients=4, pool=4,
                   warm_batch_sizes=[2])
    monkeypatch.setattr(harness, "load_traffic", lambda name: traffic)
    cell = {"name": "tiny", "config": "gcn-co", "traffic": "sat", "chips": 1}
    metrics = harness.cell_metrics(SPEC, "gcn-co.sat", traced=True)
    out = harness.run_cell(cell, _tiny("gcn-co"), 9, 1.0, True, metrics,
                           time.perf_counter(), server=_server)
    json.dumps(out)
    assert out["correct"] is True
    assert (tmp_path / "trace" / "tiny").is_dir()
    m = {k: v["value"] for k, v in out["metrics"].items()}
    if spans:
        assert m["idle_in_batch.sat"] > 0
        assert m["idle_in_batch.sat"] + m["idle_between_batches.sat"] == (
            pytest.approx(m["idle_share.sat"]))
    else:
        assert "idle_in_batch.sat" not in m
        assert "idle_between_batches.sat" not in m
    # the CPU has no peaks, so no roofline share is read
    assert "l1_update_roofline.sat" not in m
