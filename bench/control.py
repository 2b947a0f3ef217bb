#!/usr/bin/env python3
"""Readings for the driver's limits file: runs a cell on several
seeds in one process and prints, per seed, the numbers the program
gives and the numbers its control gives (the reference computed in
bfloat16 and put in the program's place). The benchmark's own runs do
not run it.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(args.workload, seed, args.seconds, False,
                           control=True, log=lambda s: None)
        if out is None:
            return 3
        result, numbers, _ = out
        control = numbers.pop("control")
        print(json.dumps({"seed": seed, "program": numbers,
                          "control": control,
                          "metrics": result["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
