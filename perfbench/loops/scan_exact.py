"""The loop of ``scan_exact`` mixes: the ``scan`` loop's closed loop, one
client, over the same kind of drawn scans, with every scan answered by
exact geometry: ``scanner.scan(bbox, refine=True, device="jax",
exact=True)``. Every answer is checked against the exact plain reference
(``reference_exact``); the log gives, per class of scan, how many of the
records whose MBR met the box (and that passed the predicate) the exact
test drops."""

from __future__ import annotations

import inspect

from perfbench import nbytes_exact, reference, reference_exact
from perfbench.harness import BENCH_DIR, load_module, log

ScanLoop = load_module(BENCH_DIR / "loops" / "scan.py").Loop


def _require_exact_scans() -> None:
    """Stop at once, before any set-up, where the program's scanner has no
    ``exact`` keyword: it cannot answer this traffic."""
    from repro.dataset import SpatialDatasetScanner

    if "exact" not in inspect.signature(SpatialDatasetScanner.scan).parameters:
        raise SystemExit("[perfbench] the program's SpatialDatasetScanner.scan "
                         "has no exact= keyword: it cannot run scan_exact")


_require_exact_scans()


class Loop(ScanLoop):
    def __init__(self, cell, rec, scanner, seed: int, seconds: float):
        super().__init__(cell, rec, scanner, seed, seconds)
        self.rec = rec

    def _scan(self, q):
        return self.sc.scan(q.bbox, refine=True, device="jax",
                            filter=self.preds.get(q.pred), exact=True)

    def check(self, out: dict, ref) -> tuple[dict, int]:
        exact = reference_exact.ExactReference(ref)
        tot = dict.fromkeys(reference.CHECKS, 0)
        n = 0
        for i, res in out["answers"]:
            q = self.queries[i % len(self.queries)]
            if res is None:
                tot["answers_failed"] += 1
                continue
            geo, extras, _ = res
            c = reference.compare(ref, exact.mask(q.bbox, q.pred), geo, extras)
            for k, v in c.items():
                tot[k] += v
            n += 1
        by: dict = {}
        for q in self.queries:
            mbr = int(ref.mask(q.bbox, q.pred).sum())
            kept = int(exact.mask(q.bbox, q.pred).sum())
            a = by.setdefault((q.selectivity, q.pred is not None), [0, 0])
            a[0] += mbr
            a[1] += mbr - kept
        log("MBR candidates the exact test drops, over the distinct scans, by "
            "(selectivity, filtered): " + "; ".join(
                f"{k}: {d} of {m}" for k, (m, d) in sorted(by.items())))
        return tot, n

    def layer_inputs(self, out: dict, pages) -> dict:
        got = super().layer_inputs(out, pages)
        per_query: dict = {}
        for i, res in out["answers"]:
            k = i % len(self.queries)
            if res is not None and k not in per_query:
                q = self.queries[k]
                per_query[k] = nbytes_exact.exact_bytes(self.rec, q.bbox,
                                                        q.pred)
        got["exact_bytes"] = sum(per_query[i % len(self.queries)]
                                 for i, res in out["answers"]
                                 if res is not None)
        return got
