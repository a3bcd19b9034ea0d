"""Benchmark harness: one module per paper table/figure + framework benches.

``PYTHONPATH=src python -m benchmarks.run [--scale S] [--only t2,t3,...]``

Prints ``name,us_per_call,derived`` CSV rows (one per measurement) followed
by per-table human summaries. Results also land in results/bench.json. A
module that fails prints an ``ERROR`` row; the rest still run, and the
harness then exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=float(os.environ.get("BENCH_SCALE", 0.25)))
    ap.add_argument("--only", default="")
    args = ap.parse_args()

    from . import (
        bench_configs,
        bench_delta_bits,
        bench_filter,
        bench_kernels,
        bench_pipeline,
        bench_rw_time,
        bench_storage,
    )

    modules = {
        "t2_storage": bench_storage,
        "t3_rw_time": bench_rw_time,
        "f8_delta_bits": bench_delta_bits,
        "f9f10_configs": bench_configs,
        "f11_filter": bench_filter,
        "kernels": bench_kernels,
        "pipeline": bench_pipeline,
    }
    only = {s.strip() for s in args.only.split(",") if s.strip()}

    all_rows = {}
    failed = []
    print("name,us_per_call,derived")
    for name, mod in modules.items():
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        try:
            rows = mod.run(scale=args.scale)
        except Exception as e:  # keep the harness alive; report the failure
            print(f"{name},0,ERROR:{type(e).__name__}:{e}", flush=True)
            import traceback
            traceback.print_exc(file=sys.stderr)
            failed.append(name)
            continue
        dt = time.perf_counter() - t0
        all_rows[name] = rows
        for r in rows:
            n = r.get("name") or f"{r.get('table','')}/{r.get('dataset','')}/" \
                                 f"{r.get('fmt', r.get('order', r.get('sort','')))}" \
                                 f"/{r.get('codec', r.get('query', r.get('encoding','')))}"
            us = 1e6 * float(r.get("s", r.get("write_s", 0.0)) or 0.0)
            derived = ";".join(
                f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}"
                for k, v in r.items()
                if k not in ("table", "dataset", "name", "s", "write_s")
            )
            print(f"{n},{us:.1f},{derived}", flush=True)
        print(f"# {name} done in {dt:.1f}s", flush=True)

    print()
    for name, mod in modules.items():
        if name in all_rows and hasattr(mod, "summarize"):
            for line in mod.summarize(all_rows[name]):
                print(line)

    os.makedirs("results", exist_ok=True)
    with open("results/bench.json", "w") as fh:
        json.dump(all_rows, fh, indent=1, default=str)
    print("\n[bench] saved results/bench.json")
    if failed:
        sys.exit(f"[bench] failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
