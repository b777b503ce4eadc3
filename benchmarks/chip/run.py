#!/usr/bin/env python3
"""Run one benchmark cell once on the chip(s) this machine holds.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

Set-up builds the weights from ``--seed`` on the device, deploys them with
calibrated activation scales, and warms every shape the cell's traffic can
produce. The window then drives the traffic through the serving engine for
``--seconds``; after it, a seeded sample of the answers is compared with the
plain reference. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown``, and last the ``checks``:
each number compared with its limit (also the last lines of standard error).

Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""
import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from chipbench import runner, spec
    cell = spec.load_cell(args.workload)
    runner.run(cell, args.seed, args.seconds, bool(args.trace), T_PROC)


if __name__ == "__main__":
    main()
