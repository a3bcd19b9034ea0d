"""What decides ``correct``: the numpy reference agrees with the program at
a tiny lake, its float32 control does not, and a run whose timed path
alters an answer where it is produced comes out not correct.

The runs here skip the harness's look for a chip: on the CPU the program's
Pallas kernels run in interpret mode.
"""

import json
import time

import numpy as np
import pytest

from perfbench import control, harness, reference

FAKE_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}

CELLS = ["pt_scan_mixed", "eb_scan_mixed"]


@pytest.fixture(scope="module")
def bench_root(tmp_path_factory):
    """A copy of BENCHMARK.json with its configuration files named by
    absolute path."""
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        c["file"] = str(harness.ROOT / c["file"])
    root = tmp_path_factory.mktemp("bench")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def tiny(workload: str, root) -> harness.Cell:
    cell = harness.Cell(workload, root=root)
    cell.cfg["n_records"] = 1500 if cell.cfg["name"] == "pt_taxi" else 60_000
    cell.mix.update(distinct=16)
    return cell


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    # the persistent compile cache is process-wide JAX state; tests leave it
    # as other tests expect it
    monkeypatch.setattr("repro.kernels.enable_compile_cache",
                        lambda: "(off in tests)")


def run(cell, seed=2**31 + 17, seconds=1.5, trace=False):
    return harness.run_cell(cell, seed, seconds, trace, time.perf_counter(),
                            dict(FAKE_DEVICE))


@pytest.mark.parametrize("workload", CELLS)
def test_program_agrees_with_reference(workload, bench_root):
    cell = tiny(workload, bench_root)
    line = run(cell)
    assert line["correct"], line["checks"]
    assert all(c["value"] == 0 for c in line["checks"].values())
    assert line["attempted"] > 0 and line["failed"] == 0
    names = {m["name"] for m in cell.end_to_end}
    assert set(line["metrics"]) == names
    assert list(line)[-1] == "checks"


def test_traced_run_reads_its_per_layer_metrics(bench_root):
    cell = tiny("pt_scan_mixed", bench_root)
    line = run(cell, trace=True)
    assert line["correct"]
    # no TPU plane in a CPU trace: the device readers still read, the
    # roofline reader finds no busy time and stays silent
    got = set(line["metrics"])
    assert {"reader.plan_ms.scan", "scanner.shards_read_pct.scan",
            "launch.compiles.scan"} <= got
    assert "refine_chain_roofline.scan" not in got
    assert line["device"]["window_s"] > 0


@pytest.mark.parametrize("workload", CELLS)
def test_float32_control_is_not_correct(workload, bench_root):
    tot = control.control_readings(tiny(workload, bench_root), 3, 40)
    assert tot["coords_wrong"] > 0


@pytest.mark.parametrize("workload", CELLS)
def test_answer_altered_where_produced_is_not_correct(monkeypatch, workload,
                                                     bench_root):
    import repro.kernels.fp_delta as fpd

    real = fpd.gather_stream_values

    def altered(*args, **kw):
        out = real(*args, **kw)
        if len(out):
            out = out.copy()
            out[0] = np.nextafter(out[0], np.inf)
        return out

    monkeypatch.setattr(fpd, "gather_stream_values", altered)
    line = run(tiny(workload, bench_root))
    assert not line["correct"]
    assert line["checks"]["coords_wrong"]["value"] > 0


def test_compare_counts_each_kind_of_fault(bench_root):
    cell = tiny("pt_scan_mixed", bench_root)
    data = cell.generator.generate(cell.cfg, 9)
    ref = reference.Records(data, cell.cfg)
    bbox = (float(np.median(ref.cx)) - 0.02, float(np.median(ref.cy)) - 0.02,
            float(np.median(ref.cx)) + 0.02, float(np.median(ref.cy)) + 0.02)
    want = ref.mask(bbox)
    geo, extras = ref.answer(bbox)
    assert want.sum() > 3
    assert not any(reference.compare(ref, want, geo, extras).values())
    # a record dropped (its slots and attributes with it)
    n0 = int(ref.vcount[np.flatnonzero(want)[0]])
    dropped = reference.Answer(geo.types[1:], geo.type_rep[1:], geo.rep[n0:],
                               geo.defn[n0:], geo.x[n0:], geo.y[n0:])
    c = reference.compare(ref, want, dropped,
                          {k: v[1:] for k, v in extras.items()})
    assert c["records_missing"] == 1 and c["records_extra"] == 0
    # an attribute altered
    bad = dict(extras, stand=extras["stand"] + 1)
    assert reference.compare(ref, want, geo, bad)["attrs_wrong"] == want.sum()
    # a level altered
    rep = geo.rep.copy()
    rep[1] = 3
    c = reference.compare(ref, want, reference.Answer(
        geo.types, geo.type_rep, rep, geo.defn, geo.x, geo.y), extras)
    assert c["levels_wrong"] == 1
