"""One run of one cell: set-up, the measured window, the check, the metrics.

Everything that belongs to one configuration, traffic mix or metric is found
by name: ``configs/<config>.json`` (sizes) with ``configs/<arch>.py`` (the
plain reference), ``traffic/<mix>.json`` (read by ``load.py``) and
``metrics/<metric>.py`` (one reader each).  This file holds only what every
cell shares.
"""
from __future__ import annotations

import asyncio
import dataclasses
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import time

import numpy as np

import check
import costs
import load
import devtrace as tr

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
GRAPH_ID = "bench-graph"
STATE = ROOT / ".bench"              # run-time state inside the checkout


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.stem.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_config(spec: dict, name: str) -> dict:
    entry = next(c for c in spec["configs"] if c["name"] == name)
    return json.loads((ROOT / entry["file"]).read_text())


def load_arch(cfg: dict):
    return _module(BENCH / "configs" / f"{cfg['arch']}.py")


def load_traffic(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def load_peak(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(known: {sorted(table)})")
    return table[kind]


def cell_metrics(spec: dict, cell: str, traced: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics, or
    with ``traced`` the per-layer metrics that list it."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if m["moves"] in names and cell in m.get("workloads", [cell])]


def require_chips(n: int) -> None:
    """Exit, printing no result, unless JAX finds ``n`` TPU chips or more."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < n:
        sys.exit(f"bench: the cell needs {n} chips, JAX found {len(devices)}")


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache, kept as the program keeps it
    (``repro.launch.compile_cache``), in a fixed directory inside the
    checkout that the benchmark gives it: only a cell's first run there
    compiles, and two checkouts share nothing.  Every program is kept,
    however quickly it compiled."""
    import jax
    from repro.launch import compile_cache
    os.environ[compile_cache.ENV] = str(ROOT / ".jax_cache")
    path = compile_cache.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def start(chips: int, t_process: float) -> None:
    """What every process of the benchmark does first on the chip."""
    require_chips(chips)
    log(f"set-up: TPU runtime up at {time.perf_counter() - t_process:.3f} s")
    import repro.core  # noqa: F401
    log(f"set-up: program imported at "
        f"{time.perf_counter() - t_process:.3f} s")
    log(f"compile cache: {enable_compile_cache()}")


def load_calibration(kind: str):
    """The engine's kernel-rate fit for a device kind, as committed in
    ``calibration.json`` (made on the chip by ``fit.py``): every checkout
    plans against the same rates, so every run of a cell takes one plan."""
    from repro.core.calibrate import CalibratedModel
    table = json.loads((BENCH / "calibration.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no kernel-rate fit for device kind {kind!r} in "
                       f"calibration.json (known: {sorted(table)})")
    return CalibratedModel(**table[kind])


class CompileCounter:
    """Counts backend compiles (a load from the persistent cache among
    them), sums their seconds and keeps the names of their programs."""

    def __init__(self):
        import jax.monitoring
        self.count, self.seconds, self.names = 0, 0.0, []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration
            self.names.append(str(kw.get("fun_name", "?")))


# ---------------------------------------------------------------- set-up
@dataclasses.dataclass
class Setup:
    cfg: dict
    arch: object
    traffic: dict
    graph: object              # graphs.Graph
    weights: dict              # name -> jax array, as served
    pool: list                 # request feature matrices (host arrays)


def make_weights(arch, cfg: dict) -> dict:
    """Glorot-normal weights from the configuration's seed, made on the
    device in one jitted call, in the dtype they are served in."""
    import jax
    import jax.numpy as jnp
    shapes = arch.weight_shapes(cfg)
    dtype = jnp.dtype(cfg["dtype"])

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(shapes))
        return {name: (jax.random.normal(k, s, dtype)
                       * np.sqrt(2.0 / (s[0] + s[1])).astype(dtype))
                for k, (name, s) in zip(keys, sorted(shapes.items()))}

    return init(jax.random.key(cfg["weight_seed"]))


def prepare(cfg: dict, traffic: dict, seed: int) -> Setup:
    import graphs
    graph = graphs.make_graph(cfg["dataset"])
    arch = load_arch(cfg)
    weights = make_weights(arch, cfg)
    pool = load.make_pool(traffic, graph.features, np.random.default_rng(
        [seed, 0]))
    return Setup(cfg, arch, traffic, graph, weights, pool)


def program_server(s: Setup, calibration: object = None):
    """The system under test: ``ServingEngine`` over a literal
    ``DynasparseEngine`` planned against the committed fit of the chip's
    kernel rates (``load_calibration``), or against ``calibration``."""
    import jax.numpy as jnp
    from repro import compat
    from repro.core import DynasparseEngine
    from repro.core.perfmodel import runtime_fallback
    from repro.core.primitives import SparseCOO
    from repro.serving import ServingConfig, ServingEngine, SharedPlanCache

    if calibration is None:
        calibration = load_calibration(compat.device_kind())
    g = s.graph
    adj = SparseCOO((g.n, g.n), jnp.asarray(g.rows), jnp.asarray(g.cols),
                    jnp.asarray(g.vals), tag="adjacency")
    engine = DynasparseEngine(runtime_fallback(), literal=True,
                              calibration=calibration,
                              cache=SharedPlanCache())
    srv = ServingEngine(s.arch.MODEL, s.weights, engine=engine,
                        config=ServingConfig(max_batch=s.cfg["max_batch"]))
    srv.register_graph(GRAPH_ID, adj)
    return srv


def describe(srv) -> None:
    """Print the calibration's provenance and each kernel's route."""
    from repro.core import calibrate
    hw = srv.engine.runtime_hw()
    rates = " ".join(f"{k}={getattr(hw, k):.4g}" for k in (
        "gemm_s_per_mac", "spdmm_s_per_mac", "spmm_s_per_mac",
        "pack_s_per_slot") if hasattr(hw, k))
    log(f"calibration: model={hw.name} calibrated={hw.calibrated} "
        f"samples={getattr(hw, 'n_samples', 0)} "
        f"measured_in_process={calibrate.measurement_count()} {rates}")
    for cm in getattr(srv, "_compiled", {}).values():
        routes = []
        for (name, r), p in zip(cm.report.kernels, cm.payload):
            kind = ("gemm" if p is None else
                    "sparse" if "xd" in p else "act")
            routes.append(f"{name}={kind}(stq={r.n_stq},dtq={r.n_dtq})")
        log("routes: " + " ".join(routes))


async def warm(infer, s: Setup, t_process: float) -> None:
    """One eager batch of ``max_batch``, then one compiled batch of each of
    the mix's ``warm_batch_sizes``."""
    mb = s.cfg["max_batch"]
    for k in [mb] + list(s.traffic["warm_batch_sizes"]):
        outs = await asyncio.gather(*[infer(s.pool[i % len(s.pool)])
                                      for i in range(k)])
        np.asarray(outs[-1])
        log(f"set-up: batch of {k} served at "
            f"{time.perf_counter() - t_process:.3f} s")


# ------------------------------------------------------------------ run
@dataclasses.dataclass
class Run:
    """What a metric reader reads."""
    cell: str
    seconds: float
    setup_s: float
    recs: list                 # load.Record per request sent in the window
    edges: dict                # window edges on the host clock
    requests: list             # the program's RequestStats, one per rec
    activation: list           # activation telemetry of the window's batches
    kernels: object            # batch -> list of costs.Kernel
    peak: dict
    events: dict | None = None  # trace events (trace.read_xplane)
    program: str = "jit_replay"  # module name prefix of the served program

    def completed(self) -> float:
        """Requests completed in the window: one for each whose logits the
        client held by the close, and for one still in flight then, the
        share of its micro-batch's run (dispatch to logits held) that lay
        inside the window, so that a batch straddling the close counts for
        the work done before it."""
        t_end, n = self.edges["t_end"], 0.0
        for rec, st in zip(self.recs, self.requests):
            if rec.logits is None:
                continue
            if rec.t_done <= t_end:
                n += 1.0
            else:
                t0 = rec.t_ready - st.t_execute
                n += min(1.0, max(0.0, (t_end - t0) / (rec.t_done - t0)))
        return n

    def batches(self) -> list[tuple[float, float, int]]:
        """``(t0, t1, k)`` on the host clock for each micro-batch of the
        window: its requests share one ``t_execute``; it ended no later than
        the first of them was handed back."""
        by: dict[float, list] = {}
        for rec, st in zip(self.recs, self.requests):
            if st.error is None and rec.logits is not None:
                by.setdefault(st.t_execute, []).append(rec.t_ready)
        return sorted((min(t) - te, min(t), len(t)) for te, t in by.items())

    def batch_ms(self) -> float | None:
        b = self.batches()
        return sum(t1 - t0 for t0, t1, _ in b) / len(b) * 1e3 if b else None

    def ops_per_request(self) -> float:
        return costs.model_ops(self.kernels(1))

    # -- on the trace's clock (nanoseconds); only in a traced run
    def window_ns(self) -> tuple[float, float]:
        return tr.window_span(self.events)

    def busy_ns(self, lo: float, hi: float) -> float:
        return sum(e - s for s, e in tr.busy(self.events, lo, hi))

    def kernel_roofline(self) -> float | None:
        """Least time of the model kernels of the window's batches (each
        weighted by the share of it inside the window) over the device time
        of the served program's operations in the window, in %."""
        if self.events is None or not self.peak:
            return None
        lo, hi = self.window_ns()
        device = tr.program_busy(self.events, lo, hi, self.program)
        if device <= 0:
            return None
        t_lo, t_hi = self.edges["t_start"], self.edges["t_end"]
        least = 0.0
        for t0, t1, k in self.batches():
            inside = min(t1, t_hi) - max(t0, t_lo)
            if inside > 0:
                least += costs.least_s(self.kernels(k), self.peak) * (
                    inside / (t1 - t0))
        return 100.0 * least * 1e9 / device


def read_metrics(run: Run, metrics: list[dict]) -> dict:
    out = {}
    for m in metrics:
        value = _module(BENCH / "metrics" / f"{m['name']}.py").read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def _infer(srv):
    async def infer(h):
        return await srv.infer(GRAPH_ID, h)
    return infer


async def _window(infer, s: Setup, seconds: float, seed: int,
                  trace_dir: str | None):
    """The measured window; traced, its span on the trace is the window."""
    import jax
    rng = np.random.default_rng([seed, 1])
    if trace_dir is None:
        return await load.run_window(infer, s.pool, s.traffic, seconds, rng)
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir, profiler_options=_profile_options())
    # an annotation made before the trace started would not be recorded
    span = []

    def open_span():
        span.append(jax.profiler.TraceAnnotation(tr.WINDOW))
        span[0].__enter__()

    try:
        return await load.run_window(
            infer, s.pool, s.traffic, seconds, rng, on_open=open_span,
            on_close=lambda: span[0].__exit__(None, None, None))
    finally:
        jax.profiler.stop_trace()


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def run_cell(cell: dict, cfg: dict, seed: int, seconds: float, traced: bool,
             metrics: list[dict], t_process: float, *,
             server=program_server, trace_dir: str | None = None) -> dict:
    """One run; returns the result line as a dict."""
    import jax
    dev = jax.devices()[0]
    compiles = CompileCounter()
    s = prepare(cfg, load_traffic(cell["traffic"]), seed)
    log(f"set-up: graph, weights and pool at "
        f"{time.perf_counter() - t_process:.3f} s")
    srv = server(s)
    infer = _infer(srv)
    if traced:
        trace_dir = trace_dir or str(STATE / "trace" / cell["name"])
    else:
        trace_dir = None

    async def main():
        await warm(infer, s, t_process)
        describe(srv)
        n_warm = len(srv.stats.requests)
        act0 = len(srv.stats.activation_batches)
        c0 = compiles.count
        t_setup = time.perf_counter() - t_process
        recs, edges = await _window(infer, s, seconds, seed, trace_dir)
        log(f"compiles: {c0} in set-up ({compiles.seconds:.3f} s), "
            f"{compiles.count - c0} inside the window "
            f"{compiles.names[c0:]}")
        reqs = sorted(srv.stats.requests, key=lambda r: r.request_id)
        reqs = [r for r in reqs if r.request_id >= n_warm]
        return (t_setup, recs, edges, reqs,
                srv.stats.activation_batches[act0:])

    try:
        t_setup, recs, edges, reqs, act = asyncio.run(main())
    finally:
        srv.close()
    stats = dev.memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    kinds = dev.device_kind
    run = Run(cell=cell["name"], seconds=seconds, setup_s=t_setup, recs=recs,
              edges=edges, requests=reqs, activation=act,
              kernels=lambda k: s.arch.kernels(cfg, len(s.graph.rows), k),
              peak=load_peak(kinds) if dev.platform == "tpu" else {})
    device = {"platform": dev.platform, "kind": kinds,
              "count": jax.device_count(), "memory_peak_bytes": peak_bytes}
    breakdown = None
    if traced:
        run.events = tr.read_xplane(trace_dir)
        lo, hi = tr.window_span(run.events)
        busy = tr.busy(run.events, lo, hi)
        device["busy_s"] = sum(e - b for b, e in busy) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        breakdown = {"device_ops": tr.op_seconds(run.events, lo, hi),
                     "idle_gaps": tr.idle_gaps(run.events, lo, hi)}
    del srv
    checks = check.judge(s, recs)
    out = {"correct": check.verdict(checks),
           "attempted": len(recs),
           "failed": int(checks["failed"]["value"]),
           "metrics": read_metrics(run, metrics),
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    errors = [r.error for r in recs if r.error is not None]
    if errors:
        log(f"{len(errors)} requests failed; the first: {errors[0]}")
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return out
