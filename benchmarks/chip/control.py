#!/usr/bin/env python3
"""Read the control of a cell's check on the chip, at the cell's own size.

    python3 benchmarks/chip/control.py --workload <name> --seed <n> \
        --seconds <s> [--control bfloat16]

Runs the cell as ``run.py`` does (set-up, a window at the cell's own load,
a seeded sample of the answers), then checks the sample with the plain
reference computed in ``--control`` in the program's place. The result
line is ``run.py``'s; its ``checks`` hold the control's numbers, which must
come out above their limits (``correct`` false). The benchmark's own runs
never run this.
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
    ap.add_argument("--control", default="bfloat16")
    args = ap.parse_args(argv)
    from chipbench import runner, spec
    runner.run(spec.load_cell(args.workload), args.seed, args.seconds, False,
               T_PROC, control=args.control)


if __name__ == "__main__":
    main()
