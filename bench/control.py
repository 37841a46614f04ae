#!/usr/bin/env python3
"""Readings that set the limits of ``correct``, on the chip, in one process.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3 \
        [--plant one_server|answer_bit|half_batch]

Runs the cell as ``bench/run.py`` does, once per seed (each its own
database, keys and traffic), with the named fault planted under the timed
path, or with none: no plant reads the program's own numbers (the lower
readings), the control ``one_server`` and the faults the upper ones. Each
run prints one JSON line with its seed, ``correct`` and the numbers
compared. The benchmark's own runs never plant anything.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import faults  # noqa: E402
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--plant", choices=sorted(faults.PLANTS))
    args = ap.parse_args(argv)
    bundle = run.load_cell(args.workload)
    device = run.prepare(bundle)
    plant = faults.PLANTS[args.plant] if args.plant else None
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        res = run.execute(bundle, seed, args.seconds, False, device=device,
                          plant=plant, t_start=t0)
        print(json.dumps({"seed": seed, "plant": args.plant,
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "checks": res["checks"],
                          "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
