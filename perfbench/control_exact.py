"""The control of ``correct`` for exact scans: the exact plain reference
computed on float32 coordinates, the precision below the configuration's
float64, put in the program's place.

As ``control.py`` does for MBR scans, for each seed it draws the cell's
scans as a run does and answers them from the float32 records, by the
exact test over those records; the float64 exact reference judges them with
the same comparison as a run. A sound comparison finds the control wrong
on every seed. Run from the root of the checkout::

    python3 perfbench/control_exact.py --workload mb_scan_exact --seeds 1,2,3

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
    from perfbench import gen, reference, reference_exact

    data = cell.generator.generate(cell.cfg, seed)
    ref = reference.Records(data, cell.cfg)
    low = reference.Records(data, cell.cfg, dtype=np.float32)
    judge = reference_exact.ExactReference(ref)
    control = reference_exact.ExactReference(low)
    rng = np.random.default_rng([seed, 2])
    queries = gen.scan_queries(ref, cell.cfg, cell.mix, rng,
                               cell.mix["distinct"])
    tot = dict.fromkeys(reference.CHECKS, 0)
    for j, q in enumerate(queries):
        times = requests // len(queries) + (j < requests % len(queries))
        if not times:
            continue
        recs = np.flatnonzero(control.mask(q.bbox, q.pred))
        iv = reference.ragged_ranges(low.vstart[recs], low.vcount[recs])
        geo = reference.Answer(low.types[recs], np.zeros(len(recs), np.uint8),
                               low.rep[iv], np.ones(len(iv), np.uint8),
                               low.x[iv].astype(np.float64),
                               low.y[iv].astype(np.float64))
        extras = {k: np.asarray(v)[recs] for k, v in low.extras.items()}
        for k, v in reference.compare(ref, judge.mask(q.bbox, q.pred),
                                      geo, extras).items():
            tot[k] += times * v
    return tot


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--requests", type=int, default=200)
    args = ap.parse_args()
    # the cell's loop checks the program's scanner when it loads
    sys.path.insert(0, str(ROOT / "src"))
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
