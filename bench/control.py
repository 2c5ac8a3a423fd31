"""The control of a cell's check: the plain reference in the program's place,
one precision below what the configuration states (``check.dot3``).

    python bench/control.py --workload gcn-co.sat --seconds 3 --seeds 1 2 3

Drives the control with the cell's own traffic, at the cell's own sizes,
for a short window per seed, and judges its answers exactly as a run judges
the program's.  Prints one JSON line per seed with the verdict and the
numbers compared; ``correct`` has to come out false on every seed.  The
benchmark's runs never run this.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

import check  # noqa: E402
import harness  # noqa: E402
import load  # noqa: E402
import numpy as np  # noqa: E402


def readings(cell: dict, cfg: dict, seed: int, seconds: float) -> dict:
    s = harness.prepare(cfg, harness.load_traffic(cell["traffic"]), seed)
    infer = check.control_infer(s)
    recs, _ = asyncio.run(load.run_window(
        infer, s.pool, s.traffic, seconds, np.random.default_rng([seed, 1])))
    checks = check.judge(s, recs)
    return {"workload": cell["name"], "seed": seed,
            "correct": check.verdict(checks), "attempted": len(recs),
            "checks": checks}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", default=[1, 2, 3])
    args = ap.parse_args(argv)
    spec = harness.load_spec()
    cell = next(w for w in spec["workloads"] if w["name"] == args.workload)
    harness.start(cell["chips"], T_PROCESS)
    cfg = harness.load_config(spec, cell["config"])
    for seed in args.seeds:
        print(json.dumps(readings(cell, cfg, seed, args.seconds)), flush=True)


if __name__ == "__main__":
    main()
