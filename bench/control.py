"""Run a cell with its control in the program's place.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 [--seconds 2]

The control is the configuration's plain reference with one guarantee
broken (``control`` in ``bench/configs/<reference>.py``: a sort on the
device whose equal keys come out in reverse input order).  It runs
through the whole of a run, at the cell's own size and on the cell's
chips, and must come out not correct: what it reads under
``mismatched_indices`` on each seed is the upper reading that the
limit of 0 is set below.  The benchmark's own runs never call this.
Prints one JSON line per seed and exits 1 when a control came out
correct.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))

import run as bench  # noqa: E402
from harness.cell import load_cell, load_module  # noqa: E402


class ControlEntry:
    """The entry with its sort replaced by the control; the rest of the
    run (data, placement, checks) is the entry's own."""

    def __init__(self, entry, control):
        self.entry, self.control = entry, control
        self.sharding = entry.sharding

    def __call__(self, x):
        return self.control(x)

    def permutation(self, out) -> np.ndarray:
        return np.asarray(out)

    def trace_count(self) -> int:
        return self.entry.trace_count()

    def faults(self) -> dict:
        return self.entry.faults()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
        BENCH_DIR.parent / ".jax_cache")
    cell = load_cell(args.workload)
    control = load_module("configs", cell.config["reference"]).control
    came_out_correct = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            out = bench.run(args.workload, seed, args.seconds, False,
                            wrap_entry=lambda e: ControlEntry(e, control))
        except bench.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        came_out_correct += int(out["correct"])
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "checks": out["checks"]}), flush=True)
    return 1 if came_out_correct else 0


if __name__ == "__main__":
    sys.exit(main())
