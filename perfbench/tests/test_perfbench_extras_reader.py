"""The reader of ``reader.extras_pages_per_batch.scan``: attribute pages a
batched decode call, on a synthetic ``ctx``, silent where the program has
none of the ``reader.extras_*`` counters, and read on a tiny traced run of
the program on the CPU."""

import json
import time

import pytest

from perfbench import harness

NAME = "reader.extras_pages_per_batch.scan"


def reader():
    return harness.load_module(harness.BENCH_DIR / "metrics" / f"{NAME}.py")


def ctx(n, counters=None):
    return {"n_requests": n, "spans": {}, "counters": counters or {}}


@pytest.mark.parametrize("pages, batches, want", [
    (48, 12, 4.0), (7, 7, 1.0), (5, 2, 2.5)])
def test_extras_pages_per_batch_reader(pages, batches, want):
    c = {"reader.extras_pages": pages, "reader.extras_batches": batches}
    assert reader().read(ctx(3, counters=c)) == pytest.approx(want)


@pytest.mark.parametrize("counters", [
    {"fp_delta.escape_pages.closed": 4},      # a program without the counters
    {},
    {"reader.extras_pages": 0, "reader.extras_batches": 0},
])
def test_extras_pages_per_batch_reader_silent(counters):
    assert reader().read(ctx(3, counters=counters)) is None


def test_extras_pages_per_batch_listed_for_the_scan_cells():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    m = {m["name"]: m for m in bench["per_layer"]}[NAME]
    assert m["moves"] == "scan_p95_ms"
    assert m["layer"] == "reader"
    assert m["source"] == "program_counter"
    assert m["workloads"] == ["pt_scan_mixed", "eb_scan_mixed",
                              "mb_scan_exact"]


def test_traced_tiny_run_reads_extras_pages_per_batch(tmp_path, monkeypatch):
    monkeypatch.setattr("repro.kernels.enable_compile_cache",
                        lambda: "(off in tests)")
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        c["file"] = str(harness.ROOT / c["file"])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.Cell("eb_scan_mixed", root=tmp_path)
    cell.cfg["n_records"] = 20_000
    cell.cfg["page_values"] = 512
    cell.mix.update(distinct=8)
    line = harness.run_cell(cell, 2**31 + 53, 1.5, True, time.perf_counter(),
                            {"platform": "cpu", "kind": "TPU v5 lite",
                             "count": 1})
    assert line["correct"]
    assert line["metrics"][NAME]["value"] >= 1.0
