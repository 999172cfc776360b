#!/usr/bin/env python3
"""Readings of a cell's controls on the chip, for setting its limits.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <a,b,c>

Runs the cell as ``run.py`` does, once per seed in one process, and
prints each run's result with ``controls`` added: the numbers compared,
read again with the reference put in the program's place at the next
lower precision than the configuration states (``high``, three passes,
for float32 at ``HIGHEST``), and
``mirror_diff.lost_write``, the mirror against a reference that lost the
last acknowledged write.  The benchmark's own runs never compute these.
"""
from __future__ import annotations

import argparse
import json
import sys

from run import open_cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    harness, cell, device = open_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(cell, seed, args.seconds, False, device=device,
                               controls=True)
        print(json.dumps({"seed": seed, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
