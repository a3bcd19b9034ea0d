"""The ``mb_scan_exact`` cell: the MB generator gives the same work for every
seed, a tiny run of the cell is correct, the comparison catches an answer
altered where the exact stage produces it and MBR answers in place of
exact ones, the byte function counts by hand, and the new per-layer
readers read their spans and counters (and stay silent without them).

Runs skip the harness's look for a chip: on the CPU the program's kernels
run in interpret mode.
"""

import json
import time

import numpy as np
import pytest

from perfbench import gen, harness, nbytes_exact, reference, reference_exact

FAKE_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
CELL = "mb_scan_exact"
NEW = {"reader.exact_ms.scan": "reader",
       "exact.host_edges_pct.scan": "exact refine",
       "exact.mbr_false_pct.scan": "exact refine",
       "exact_stage_roofline.scan": "kernels"}
# a seed whose tiny lake has MBR-only candidates among the first scans
SEED = 2**31 + 11


@pytest.fixture(scope="module")
def bench_root(tmp_path_factory):
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        c["file"] = str(harness.ROOT / c["file"])
    root = tmp_path_factory.mktemp("bench")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    monkeypatch.setattr("repro.kernels.enable_compile_cache",
                        lambda: "(off in tests)")


def tiny(root) -> harness.Cell:
    cell = harness.Cell(CELL, root=root)
    cell.cfg["n_records"] = 20_000
    cell.mix.update(distinct=32)
    return cell


def run(cell, seed=SEED, trace=False):
    return harness.run_cell(cell, seed, 1.5, trace, time.perf_counter(),
                            dict(FAKE_DEVICE))


# ---------------------------------------------------------- the generator
def test_generator_is_deterministic_with_the_same_work_per_seed(bench_root):
    cell = tiny(bench_root)
    gen_mod = cell.generator
    a = gen_mod.generate(cell.cfg, 5)
    b = gen_mod.generate(cell.cfg, 5)
    c = gen_mod.generate(cell.cfg, 2**31 + 5)
    assert np.array_equal(a["coords"], b["coords"])
    assert not np.array_equal(a["coords"][:100], c["coords"][:100])
    for k in ("part_sizes", "parts_per_record"):
        # the same rings and points, in another order
        assert np.array_equal(np.sort(a[k]), np.sort(c[k]))
    n = cell.cfg["n_records"]
    assert list(gen_mod.shape_counts(n)) == [15_400, 4_000, 600]
    assert len(a["coords"]) == len(c["coords"]) == 5 * 15_400 + 7 * 4_000 + (
        10 * 600)
    assert gen_mod.town_sizes(n).sum() == n
    # the towns stand at the same places: the first building of the first
    # town sits at its centre's lot for both seeds
    lon, lat, _, _ = gen_mod.town_map()
    for d in (a, c):
        assert np.abs(d["coords"][0] - (lon[0], lat[0])).max() < 0.01
    # shells clockwise, holes counter-clockwise, every ring closed
    starts = np.cumsum(a["part_sizes"]) - a["part_sizes"]
    co = a["coords"]
    assert np.array_equal(co[starts], co[starts + a["part_sizes"] - 1])
    hole = np.zeros(len(starts), bool)
    hole[np.cumsum(a["parts_per_record"])[a["parts_per_record"] == 2] - 1] = True
    area = [0.5 * np.sum(co[s:s + k - 1, 0] * co[s + 1:s + k, 1]
                         - co[s + 1:s + k, 0] * co[s:s + k - 1, 1])
            for s, k in zip(starts[:3000], a["part_sizes"][:3000])]
    assert ((np.array(area) > 0) == hole[:3000]).all()
    # the filter keeps about half
    h = a["extras"]["height"]
    flt = cell.cfg["filter"]
    assert 0.45 < np.mean((h >= flt["lo"]) & (h <= flt["hi"])) < 0.55


def test_scan_exact_mix_gives_every_seed_the_same_work(bench_root):
    cell = tiny(bench_root)
    data = cell.generator.generate(cell.cfg, 3)
    rec = reference.Records(data, cell.cfg)
    comp = []
    for seed in (1, 2**31 + 5):
        qs = gen.scan_queries(rec, cell.cfg, cell.mix,
                              np.random.default_rng([seed, 2]), 16)
        comp.append(sorted((q.selectivity, q.pred is not None) for q in qs))
    assert comp[0] == comp[1]
    assert {s for s, _ in comp[0]} == {0.001, 0.01}


# --------------------------------------------------------------- the runs
def test_tiny_run_is_correct(bench_root):
    cell = tiny(bench_root)
    line = run(cell)
    assert line["correct"], line["checks"]
    assert all(c["value"] == 0 for c in line["checks"].values())
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"scan_p95_ms", "stored_bytes_per_point",
                                    "setup_s"}


def test_answer_altered_in_the_exact_stage_is_not_correct(monkeypatch,
                                                          bench_root):
    import repro.kernels.exact as dev_exact

    real = dev_exact.classify_device

    def altered(*args, **kw):
        cls, vertex = real(*args, **kw)
        cls = cls.copy()
        if len(cls):  # the first kept record's class flips in and out
            cls[0] = 0 if cls[0] == 1 else 1
        return cls, vertex

    monkeypatch.setattr(dev_exact, "classify_device", altered)
    line = run(tiny(bench_root))
    assert not line["correct"]
    assert (line["checks"]["records_missing"]["value"]
            + line["checks"]["records_extra"]["value"]) > 0


def test_mbr_answers_in_place_of_exact_are_not_correct(monkeypatch,
                                                       bench_root):
    cell = tiny(bench_root)

    def mbr_scan(self, q):
        return self.sc.scan(q.bbox, refine=True, device="jax",
                            filter=self.preds.get(q.pred))

    monkeypatch.setattr(cell.loop, "_scan", mbr_scan)
    line = run(cell)
    assert not line["correct"]
    assert line["checks"]["records_extra"]["value"] > 0
    assert line["checks"]["records_missing"]["value"] == 0


def test_traced_tiny_run_reads_the_new_metrics(bench_root):
    line = run(tiny(bench_root), trace=True)
    assert line["correct"]
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # no TPU plane in a CPU trace: the roofline reader stays silent
    assert set(NEW) - {"exact_stage_roofline.scan"} <= set(m)
    assert "exact_stage_roofline.scan" not in m
    assert m["reader.exact_ms.scan"] > 0
    assert 0.0 <= m["exact.host_edges_pct.scan"] < 100.0
    assert 0.0 <= m["exact.mbr_false_pct.scan"] < 100.0
    assert {"reader.plan_ms.scan", "launch.compiles.scan",
            "device.idle_pct.scan"} <= set(m)


def test_parent_without_exact_scans_stops_at_once(monkeypatch):
    from repro.dataset import SpatialDatasetScanner

    def scan(self, bbox=None, columns=None, refine=False, parallel=True,
             coalesce=True, device="cpu", *, keep_on_device=False,
             filter=None):
        raise AssertionError("never reached")

    monkeypatch.setattr(SpatialDatasetScanner, "scan", scan)
    with pytest.raises(SystemExit, match="exact"):
        harness.load_module(harness.BENCH_DIR / "loops" / "scan_exact.py")


# ------------------------------------------------ reference and the bytes
def _hand_records():
    # three footprints: a square at the origin, an L farther east, a square
    # with a courtyard in the north; ids 0, 1, 2
    sq = [(0, 0), (0, 2), (2, 2), (2, 0), (0, 0)]
    ell = [(10, 0), (10, 4), (12, 4), (12, 2), (14, 2), (14, 0), (10, 0)]
    shell = [(0, 10), (0, 16), (6, 16), (6, 10), (0, 10)]
    hole = [(2, 12), (4, 12), (4, 14), (2, 14), (2, 12)]
    data = {"types": np.full(3, 3, np.uint8),
            "coords": np.array(sq + ell + shell + hole, np.float64),
            "part_sizes": np.array([5, 7, 5, 5]),
            "parts_per_record": np.array([1, 1, 2]),
            "extras": {"height": np.array([4.0, 6.0, 8.0], np.float32),
                       "bldg_id": np.arange(3, dtype=np.int64)}}
    return reference.Records(data, {"id_column": "bldg_id"})


def test_exact_bytes_hand_counted():
    rec = _hand_records()
    # meets the square's and the courtyard building's MBRs
    assert nbytes_exact.exact_bytes(rec, (1, 1, 3, 11), None) == (
        16 * (5 + 10) + 2)
    assert nbytes_exact.exact_bytes(rec, (1, 1, 3, 11),
                                    ("height", 5.0, 9.0)) == 16 * 10 + 1
    assert nbytes_exact.exact_bytes(rec, (50, 50, 60, 60), None) == 0


def test_reference_exact_hand_cases():
    rec = _hand_records()
    ref = reference_exact.ExactReference(rec)
    cases = [((1, 1, 3, 3), [0]),              # a vertex inside
             ((12.5, 2.5, 13.5, 3.5), []),     # in the L's notch
             ((12.5, 1.0, 13.5, 3.5), [1]),    # an edge crosses, no vertex
             ((2.5, 12.5, 3.5, 13.5), []),     # inside the courtyard
             ((0.5, 10.5, 1.0, 11.0), [2]),    # inside the shell
             ((4.0, 14.0, 5.0, 15.0), [2])]    # touches the hole's corner
    for bbox, want in cases:
        got = np.flatnonzero(ref.mask(bbox))
        assert got.tolist() == want, bbox
        assert set(want) <= set(np.flatnonzero(rec.mask(bbox)))
    # MBR candidates the exact answer drops are the notch and the courtyard
    assert rec.mask((12.5, 2.5, 13.5, 3.5)).sum() == 1


# ---------------------------------------------------------- the readers
def reader(name):
    return harness.load_module(harness.BENCH_DIR / "metrics" / f"{name}.py")


def ctx(n, spans=None, counters=None, **kw):
    return dict({"n_requests": n, "spans": spans or {},
                 "counters": counters or {}}, **kw)


def test_exact_ms_reader():
    r = reader("reader.exact_ms.scan")
    assert r.read(ctx(4, spans={"rg.exact": [0.002, 0.006],
                                "rg.plan": [1.0]})) == pytest.approx(2.0)
    assert r.read(ctx(0, spans={"rg.exact": [0.01]})) is None
    assert r.read(ctx(4, spans={"rg.plan": [0.01]})) is None


@pytest.mark.parametrize("name, num, den", [
    ("exact.host_edges_pct.scan", "exact.edges_host", "exact.edges"),
    ("exact.mbr_false_pct.scan", "exact.records_dropped", "exact.records_in"),
])
def test_exact_share_readers(name, num, den):
    r = reader(name)
    assert r.read(ctx(3, counters={num: 3, den: 400})) == pytest.approx(0.75)
    assert r.read(ctx(3, counters={num: 0, den: 9})) == 0.0
    assert r.read(ctx(3, counters={den: 9, "jit.compiles": 0})) is None
    assert r.read(ctx(3, counters={num: 0, den: 0})) is None
    assert r.read(ctx(3)) is None   # a program without the counters


def test_exact_stage_roofline_reader():
    r = reader("exact_stage_roofline.scan")
    peak = {"hbm_bytes_per_s": 819e9}
    trace = {"module_busy_s": {"jit_exact_fn": 0.5, "jit_fn": 9.0}}
    got = r.read(ctx(1, trace=trace, peak=peak, exact_bytes=819e9 * 0.05))
    assert got == pytest.approx(10.0)
    assert r.read(ctx(1, trace={"module_busy_s": {"jit_fn": 1.0}}, peak=peak,
                      exact_bytes=100)) is None
    assert r.read(ctx(1, trace=trace, peak=peak)) is None


def test_cell_and_metrics_listed():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL] == {**cells[CELL], "config": "mb_buildings",
                           "traffic": "scan_exact", "chips": 1}
    cfg = {c["name"]: c for c in bench["configs"]}["mb_buildings"]
    assert cfg["reduced"] == ["n_records"]
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name, layer in NEW.items():
        m = per_layer[name]
        assert (m["moves"], m["layer"], m["workloads"]) == (
            "scan_p95_ms", layer, [CELL])
        assert (harness.BENCH_DIR / "metrics" / f"{name}.py").exists()
    for name in ("device.idle_pct.scan", "reader.plan_ms.scan",
                 "launch.compiles.scan", "refine_chain_roofline.scan"):
        assert per_layer[name]["workloads"] == ["pt_scan_mixed",
                                                "eb_scan_mixed", CELL]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["scan_p95_ms"]["workloads"][-1] == CELL


def test_float32_control_is_not_correct(bench_root):
    from perfbench import control_exact

    tot = control_exact.control_readings(tiny(bench_root), 3, 40)
    assert tot["coords_wrong"] > 0
