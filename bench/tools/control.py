"""Read a cell's control, on the chip at the cell's own size, on several
seeds in one process:

    python bench/tools/control.py --workload <cell> --seconds <s> <seed>...

Each seed is one whole run of the cell (the program's readings are noted
on standard error as ``reading program``), after which the reference in
float8 is put in the program's place; for a train cell the half-batch
fault is read in the reference as well. The result line's checks are the
control's numbers, so a sound limit makes every line ``correct: false``.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", default="10")
    ap.add_argument("seeds", nargs="+")
    args = ap.parse_args()
    from bench.lib import harness as H
    run = H.load_module(H.BENCH / "run.py")
    for seed in args.seeds:
        run.main(["--workload", args.workload, "--seed", seed,
                  "--seconds", args.seconds, "--trace", "0"],
                 fault="control_fp8")


if __name__ == "__main__":
    main()
