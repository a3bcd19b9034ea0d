"""Run one cell of the lake's chip benchmark once and print its result.

    python3 perfbench/run.py --workload pt_scan_mixed --seed 7 --seconds 30 --trace 0

The cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout. The last line of standard
output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last the
numbers compared with their limits under ``checks``); progress and the
same checks go to standard error. Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from perfbench import harness

    cell = harness.Cell(args.workload)
    device = harness.device_info(cell.spec["chips"])
    line = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                            T_START, device)
    for k, c in line["checks"].items():
        print(f"check {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct = {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
