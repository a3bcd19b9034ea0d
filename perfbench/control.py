"""The control of ``correct``: the plain reference computed in float32, the
precision below the configurations' float64, put in the program's place.

For each seed it draws the cell's scans as a run does (the mix's
``distinct`` scans at the cell's own size, cycled through for
``--requests`` answers, as many as a window completes) and answers them
with the float32 reference; the float64 reference judges them with the
same comparison as a run. A sound comparison finds the control wrong on
every seed. Run on the chip's host, from the root of the checkout::

    python3 perfbench/control.py --workload pt_scan_mixed --seeds 1,2,3

It prints one JSON line per seed with the numbers compared. The benchmark's
own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def control_readings(cell, seed: int, requests: int) -> dict:
    from perfbench import gen, reference

    data = cell.generator.generate(cell.cfg, seed)
    ref = reference.Records(data, cell.cfg)
    low = reference.Records(data, cell.cfg, dtype=np.float32)
    rng = np.random.default_rng([seed, 2])
    queries = gen.scan_queries(ref, cell.cfg, cell.mix, rng,
                               cell.mix["distinct"])
    tot = dict.fromkeys(reference.CHECKS, 0)
    for j, q in enumerate(queries):
        times = requests // len(queries) + (j < requests % len(queries))
        if not times:
            continue
        geo, extras = low.answer(q.bbox, q.pred)
        for k, v in reference.compare(ref, ref.mask(q.bbox, q.pred),
                                      geo, extras).items():
            tot[k] += times * v
    return tot


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--requests", type=int, default=200)
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT))
    from perfbench.harness import Cell

    cell = Cell(args.workload)
    for s in args.seeds.split(","):
        tot = control_readings(cell, int(s), args.requests)
        print(json.dumps({"workload": args.workload, "seed": int(s),
                          "control": tot}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
