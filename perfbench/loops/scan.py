"""The loop of ``scan`` mixes: closed loop, one client,
``scanner.scan(bbox, refine=True, device="jax")`` back to back over the
mix's ``distinct`` scans drawn from the seed, cycling through them; every
answer is kept for the check. Set-up runs each of them once, so every shape
the window uses is compiled before it opens."""

from __future__ import annotations

import statistics
import time
import traceback

import numpy as np

from perfbench import gen, nbytes, reference
from perfbench.harness import annotate, log, percentile


class Loop:
    def __init__(self, cell, rec, scanner, seed: int, seconds: float):
        from repro.core.filters import Range

        self.sc = scanner
        self.mix = cell.mix
        self.queries = gen.scan_queries(rec, cell.cfg, self.mix,
                                        np.random.default_rng([seed, 2]),
                                        self.mix["distinct"])
        self.preds = {q.pred: Range(*q.pred) for q in self.queries
                      if q.pred is not None}
        sel = {}
        for q in self.queries:
            sel.setdefault(q.selectivity, []).append(
                float(rec.mask(q.bbox, q.pred).mean()))
        log("realised record selectivity by class: "
            + ", ".join(f"{k:g}: median {statistics.median(v):.5f} "
                        f"[{min(v):.5f}, {max(v):.5f}] n={len(v)}"
                        for k, v in sorted(sel.items())))

    def _scan(self, q):
        return self.sc.scan(q.bbox, refine=True, device="jax",
                            filter=self.preds.get(q.pred))

    def warm(self) -> None:
        for q in self.queries:
            self._scan(q)

    def window(self, seconds: float) -> dict:
        lat, answers, failed = [], [], 0
        t0 = time.perf_counter()
        t_end = t0 + seconds
        i = 0
        while time.perf_counter() < t_end:
            q = self.queries[i % len(self.queries)]
            s = time.perf_counter()
            try:
                with annotate("bench.scan"):
                    res = self._scan(q)
            except Exception:
                failed += 1
                log("scan failed:\n" + traceback.format_exc())
                res = None
            lat.append(time.perf_counter() - s)
            answers.append((i, res))
            i += 1
        return {"latencies_s": lat, "answers": answers, "failed": failed,
                "elapsed_s": time.perf_counter() - t0}

    def e2e(self, out: dict) -> dict:
        ms = [v * 1e3 for v in out["latencies_s"]]
        log(f"scans in window: {len(ms)}, p50 {percentile(ms, 50)} ms, "
            f"p95 {percentile(ms, 95)} ms, max {max(ms)} ms")
        by: dict = {}
        for (i, _), v in zip(out["answers"], ms):
            q = self.queries[i % len(self.queries)]
            by.setdefault((q.selectivity, q.pred is not None), []).append(v)
        log("scan ms by (selectivity, filtered): " + "; ".join(
            f"{k}: n={len(v)} p50 {percentile(v, 50):.1f} "
            f"p90 {percentile(v, 90):.1f} max {max(v):.1f}"
            for k, v in sorted(by.items())))
        return {"scan_p95_ms": percentile(ms, 95)}

    def check(self, out: dict, ref) -> tuple[dict, int]:
        tot = dict.fromkeys(reference.CHECKS, 0)
        n = 0
        for i, res in out["answers"]:
            q = self.queries[i % len(self.queries)]
            if res is None:
                tot["answers_failed"] += 1
                continue
            geo, extras, _ = res
            c = reference.compare(ref, ref.mask(q.bbox, q.pred), geo, extras)
            for k, v in c.items():
                tot[k] += v
            n += 1
        return tot, n

    def layer_inputs(self, out: dict, pages) -> dict:
        done = [(i, res) for i, res in out["answers"] if res is not None]
        nb = 0
        for i, _ in done:
            q = self.queries[i % len(self.queries)]
            nb += nbytes.chain_bytes(pages, q.bbox, q.pred)
        return {"read_stats": [res[2] for _, res in done],
                "chain_bytes": nb}

    def close(self) -> None:
        pass
