"""The per-layer readers of the reader's plan steps and the launch chain:
each on a synthetic ``ctx``, silent where the program records nothing
(no scans in the window, or a program without the span or counter), and
all seven read on a tiny traced run of the program on the CPU."""

import json
import time

import pytest

from perfbench import harness

NEW = ["reader.plan_cpu_ms.scan", "reader.crc_ms.scan",
       "reader.stream_plan_ms.scan", "reader.extras_ms.scan",
       "launch.prep_ms.scan", "launch.device_wait_ms.scan",
       "launch.padding_pct.scan"]

SPAN_OF = {"reader.crc_ms.scan": "rg.crc",
           "reader.stream_plan_ms.scan": "rg.stream_plan",
           "reader.extras_ms.scan": "rg.extras",
           "launch.prep_ms.scan": "launch.build",
           "launch.device_wait_ms.scan": "device.wait"}


def reader(name):
    return harness.load_module(harness.BENCH_DIR / "metrics" / f"{name}.py")


def ctx(n, spans=None, counters=None):
    return {"n_requests": n, "spans": spans or {}, "counters": counters or {}}


@pytest.mark.parametrize("name", sorted(SPAN_OF))
def test_span_reader_ms_per_scan(name):
    span = SPAN_OF[name]
    got = reader(name).read(ctx(4, spans={span: [0.010, 0.030],
                                          "rg.plan": [9.0]}))
    assert got == pytest.approx(1e3 * 0.040 / 4)
    assert reader(name).read(ctx(0, spans={span: [0.01]})) is None
    assert reader(name).read(ctx(4, spans={"rg.plan": [0.01]})) is None


def test_plan_cpu_reader():
    r = reader("reader.plan_cpu_ms.scan")
    got = r.read(ctx(5, counters={"cpu_ns.rg.plan": 250_000_000,
                                  "cpu_ns.rg.crc": 7}))
    assert got == pytest.approx(50.0)
    assert r.read(ctx(5, counters={"cpu_ns.rg.plan": 0})) == 0.0
    assert r.read(ctx(0, counters={"cpu_ns.rg.plan": 10})) is None
    assert r.read(ctx(5, counters={"jit.compiles": 0})) is None


def test_padding_reader():
    r = reader("launch.padding_pct.scan")
    got = r.read(ctx(3, counters={"launch.values": 3300,
                                  "launch.values_padded": 4096}))
    assert got == pytest.approx(100.0 * (1 - 3300 / 4096))
    assert r.read(ctx(3, counters={"launch.values": 8,
                                   "launch.values_padded": 8})) == 0.0
    assert r.read(ctx(3)) is None
    assert r.read(ctx(3, counters={"launch.values": 0,
                                   "launch.values_padded": 0})) is None


def test_new_metrics_listed_for_the_scan_cells():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = per_layer[name]
        assert m["moves"] == "scan_p95_ms"
        assert m["workloads"] == ["pt_scan_mixed", "eb_scan_mixed"]
        assert (harness.BENCH_DIR / "metrics" / f"{name}.py").exists()


def test_traced_tiny_run_reads_the_new_metrics(tmp_path, monkeypatch):
    monkeypatch.setattr("repro.kernels.enable_compile_cache",
                        lambda: "(off in tests)")
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        c["file"] = str(harness.ROOT / c["file"])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.Cell("pt_scan_mixed", root=tmp_path)
    cell.cfg["n_records"] = 1500
    cell.mix.update(distinct=16)
    line = harness.run_cell(cell, 2**31 + 29, 1.5, True, time.perf_counter(),
                            {"platform": "cpu", "kind": "TPU v5 lite",
                             "count": 1})
    assert line["correct"]
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(m)
    assert 0.0 <= m["launch.padding_pct.scan"] < 100.0
    # a span's thread CPU time never exceeds its wall time
    assert 0.0 < m["reader.plan_cpu_ms.scan"] <= m["reader.plan_ms.scan"]
    assert m["reader.stream_plan_ms.scan"] <= m["reader.plan_ms.scan"]
