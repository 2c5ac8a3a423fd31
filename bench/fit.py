"""Fit the engine's kernel rates on the chip, for ``calibration.json``.

    python bench/fit.py --fits 3 --repeats 30 --out bench/calibration.json

The engine routes every task of a kernel (block-skip or dense GEMM) by a
model of the chip's kernel rates.  Left to itself it fits that model once per
checkout from a short sweep, so two checkouts can serve one cell with
different plans.  The benchmark plans against one fit per device kind,
committed as data: this script makes it.  It runs the program's own sweep
(``repro.core.calibrate.calibrate``) ``--fits`` times with ``--repeats``
timings per point, prints each fit, and writes the median of every rate,
keyed by the device kind, beside the fits it came from.  The benchmark's
runs never run this.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

import harness  # noqa: E402


def median_fit(fits: list[dict]) -> dict:
    """Every number the median over the fits; the rest from the first."""
    out = dict(fits[0])
    for key, value in out.items():
        if isinstance(value, float):
            out[key] = statistics.median(f[key] for f in fits)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--fits", type=int, default=3)
    ap.add_argument("--repeats", type=int, default=30)
    ap.add_argument("--out", default=os.path.join(HERE, "calibration.json"))
    args = ap.parse_args(argv)
    harness.start(1, T_PROCESS)
    from repro import compat
    from repro.core import calibrate
    from repro.core.perfmodel import runtime_fallback
    base = runtime_fallback()
    fits = []
    for i in range(args.fits):
        t0 = time.perf_counter()
        fit = dataclasses.asdict(calibrate.calibrate(
            base, repeats=args.repeats, seed=i))
        harness.log(f"fit {i}: {time.perf_counter() - t0:.1f} s "
                    + json.dumps(fit))
        fits.append(fit)
    kind = compat.device_kind()
    doc = {"how": (f"python bench/fit.py --fits {args.fits} "
                   f"--repeats {args.repeats}: the median of each rate"),
           "devices": {kind: median_fit(fits)},
           "fits": {kind: fits}}
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(json.dumps(doc["devices"]), flush=True)


if __name__ == "__main__":
    main()
