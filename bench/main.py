"""Run one cell of the benchmark once and print its result line.

    python bench/main.py --workload gcn-co.sat --seed 7 --seconds 10 --trace 0

The cell (``workloads`` in ``BENCHMARK.json``) names a configuration and a
traffic mix.  Set-up builds the graph, the weights and the request pool,
starts the serving engine and warms every shape the mix uses; then the mix
drives ``ServingEngine.infer`` for ``--seconds``.  Every answer of the window
is checked against a plain float64 reference.  ``--trace 1`` records a
profiler trace of the window and reports the per-layer metrics instead of
the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``, each number compared with its limit.
The run exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
# the TPU runtime would log to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="where the profiler writes (default: .bench/trace)")
    return ap.parse_args(argv)


def main(argv=None) -> None:
    args = parse(argv)
    spec = harness.load_spec()
    cell = next((w for w in spec["workloads"] if w["name"] == args.workload),
                None)
    if cell is None:
        sys.exit(f"bench: no workload {args.workload!r} in BENCHMARK.json")
    harness.start(cell["chips"], T_PROCESS)
    out = harness.run_cell(
        cell, harness.load_config(spec, cell["config"]), args.seed,
        args.seconds, bool(args.trace),
        harness.cell_metrics(spec, cell["name"], bool(args.trace)),
        T_PROCESS, trace_dir=args.trace_dir)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
