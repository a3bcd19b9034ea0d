"""The reader of ``reader.escape_closed_pct.scan``: the share of FP-delta
pages whose escapes were resolved in closed form, on a synthetic ``ctx``,
silent where the program has none of the ``fp_delta.escape_pages.*``
counters, and read on a tiny traced run of the program on the CPU."""

import json
import time

import pytest

from perfbench import harness

NAME = "reader.escape_closed_pct.scan"


def reader():
    return harness.load_module(harness.BENCH_DIR / "metrics" / f"{NAME}.py")


def ctx(n, counters=None):
    return {"n_requests": n, "spans": {}, "counters": counters or {}}


@pytest.mark.parametrize("counts, want", [
    ({"closed": 45, "hop": 4, "walk": 1}, 90.0),
    ({"hop": 7}, 0.0),
    ({"closed": 2}, 100.0),
    ({"walk": 3}, 0.0),
])
def test_escape_closed_reader(counts, want):
    c = {f"fp_delta.escape_pages.{k}": v for k, v in counts.items()}
    assert reader().read(ctx(3, counters=c)) == pytest.approx(want)


@pytest.mark.parametrize("counters", [
    {"launch.values": 8},                     # a program without the counters
    {},
    {"fp_delta.escape_pages.closed": 0, "fp_delta.escape_pages.hop": 0},
])
def test_escape_closed_reader_silent(counters):
    assert reader().read(ctx(3, counters=counters)) is None


def test_escape_closed_listed_for_the_scan_cells():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    m = {m["name"]: m for m in bench["per_layer"]}[NAME]
    assert m["moves"] == "scan_p95_ms"
    assert m["layer"] == "reader"
    assert m["workloads"] == ["pt_scan_mixed", "eb_scan_mixed"]


def test_traced_tiny_run_reads_escape_closed(tmp_path, monkeypatch):
    monkeypatch.setattr("repro.kernels.enable_compile_cache",
                        lambda: "(off in tests)")
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        c["file"] = str(harness.ROOT / c["file"])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.Cell("pt_scan_mixed", root=tmp_path)
    cell.cfg["n_records"] = 1500
    cell.mix.update(distinct=16)
    line = harness.run_cell(cell, 2**31 + 41, 1.5, True, time.perf_counter(),
                            {"platform": "cpu", "kind": "TPU v5 lite",
                             "count": 1})
    assert line["correct"]
    # the coordinate pages' escapes are resolved in closed form
    assert line["metrics"][NAME]["value"] >= 90.0
