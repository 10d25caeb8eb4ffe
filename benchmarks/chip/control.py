#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, on the chip.

    python3 benchmarks/chip/control.py --workload <name> \
        --seeds 11,12,13 --seconds <s>

For each seed, in one process: one run of the cell as `run.py` makes it
(the program's numbers), plus the numbers of the control and of the planted
faults on the same inputs and protocol events, each put in the program's
place and judged against the cell's limits:

  * `fp8`: the reference with float8 (e4m3) matmul inputs;
  * `half_batch`: the reference fed half of each batch;
  * `no_exchange`: the reference whose regions never receive a delivered
    fragment (the exchange between regions left out).

Prints one JSON line per seed. The benchmark's own runs never compute these.
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    run.device.enable_compile_cache(run.ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            out = run.run_cell(args.workload, seed, args.seconds, False,
                               control=True)
        except run.device.NoChip as e:
            print(f"control.py: {e}", file=sys.stderr, flush=True)
            return 3
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "checks": out["checks"],
                          "control": out["control"],
                          "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
