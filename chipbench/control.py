"""Read a cell's numbers for its limits on the chip: the program's, and
the control's, the plain reference put in the program's place in float8,
the nearest precision below the bfloat16 the configurations state.

    python chipbench/control.py --workload <cell> --seconds <s> \\
        --seeds a b c ... [--control <n>]

Each seed runs the cell once in this process (a short window at the
cell's own load) and prints the program's verdict and the numbers its
``correct`` compares beside the cell's limits.  The first ``--control``
seeds (all, by default) also put the control's output in the program's
place and print its verdict, reached by the same comparison.  A limit is
sound when every control reads ``correct`` false and every program run
reads it true.  The benchmark's own runs never read the control.
"""
from __future__ import annotations

import argparse
import json
import sys

import bench


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=None,
                    help="how many of the seeds also judge the control")
    args = ap.parse_args(argv)
    n_control = len(args.seeds) if args.control is None else args.control
    for i, seed in enumerate(args.seeds):
        ns = bench.parse_args(["--workload", args.workload, "--seed",
                               str(seed), "--seconds", str(args.seconds)])
        out = bench.run(ns, control=i < n_control)
        line = {"seed": seed, "correct": out["correct"],
                "checks": out["checks"]}
        if "control" in out:
            line["control"] = out["control"]
        print(json.dumps(line), flush=True)
        bench.free_device_state()
    return 0


if __name__ == "__main__":
    sys.exit(main())
