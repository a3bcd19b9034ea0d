"""The reduction from a profiler trace to busy time, idle share, op
durations and named idle gaps, and the table of peaks."""

import pytest

from perfbench import xtrace


def events():
    ev = xtrace.TraceEvents()
    ev.device["/device:TPU:0"] = [
        ("jit_fn/fusion.1", 100, 300),
        ("jit_fn/fusion.2", 250, 400),      # overlaps the first
        ("jit__lambda/gather", 600, 700),
        ("jit_fn/fusion.1", 950, 1200),     # crosses the window's end
    ]
    ev.host = [("bench.scan", 0, 500), ("bench.sleep", 500, 1000),
               ("bench.scan", 580, 620)]
    return ev


def test_busy_union_idle_and_ops():
    red = xtrace.reduce(events(), 0, 1000)
    # union: [100, 400) + [600, 700) + [950, 1000)
    assert red["busy_s"] == pytest.approx(450e-9)
    assert red["window_s"] == pytest.approx(1000e-9)
    ops = dict(red["device_ops"])
    assert ops["jit_fn/fusion.1"] == pytest.approx(250e-9)
    assert ops["jit_fn/fusion.2"] == pytest.approx(150e-9)
    assert ops["jit__lambda/gather"] == pytest.approx(100e-9)
    # each module's own union: [100, 400) + [950, 1000), and [600, 700)
    assert red["module_busy_s"] == pytest.approx(
        {"jit_fn": 350e-9, "jit__lambda": 100e-9})
    gaps = dict(red["idle_gaps"])
    # gaps [0,100) and [400,600) mid in bench.scan... [400, 600) has its
    # middle at 500: bench.sleep; [700, 950) in bench.sleep
    assert gaps["bench.scan"] == pytest.approx(100e-9)
    assert gaps["bench.sleep"] == pytest.approx(450e-9)


def test_window_clipping_and_no_device_work():
    red = xtrace.reduce(events(), 300, 650)
    assert red["busy_s"] == pytest.approx(150e-9)
    empty = xtrace.TraceEvents()
    assert xtrace.reduce(empty, 0, 10)["busy_s"] == 0.0
    with pytest.raises(ValueError):
        xtrace.reduce(empty, 10, 10)


def test_peaks_are_keyed_by_device_kind():
    assert xtrace.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        xtrace.peak("TPU v99")


def test_recorded_v5e_trace():
    """A trace recorded on one TPU v5e: three rounds of a jitted cumsum and
    a take under ``bench.scan``, each followed by a 2 ms ``bench.sleep``.
    The window is the one the recording script took on the wall clock."""
    from pathlib import Path

    from jax.profiler import ProfileData

    path = Path(__file__).parent / "data" / "tiny_v5e.xplane.pb"
    ev = xtrace.from_profile(ProfileData.from_file(str(path)))
    t0, t1 = 1792191504355601223, 1792191504369020223
    assert list(ev.device) == ["/device:TPU:0"]
    ops = ev.device["/device:TPU:0"]
    assert len(ops) == 48 and all(n.startswith("jit__lambda/") for n, _, _ in ops)
    assert [n for n, _, _ in ev.host] == ["bench.scan", "bench.sleep"] * 3
    red = xtrace.reduce(ev, t0 - ev.start_ns, t1 - ev.start_ns)
    assert red["window_s"] == pytest.approx(0.013419)
    assert red["busy_s"] == pytest.approx(221.328e-6)
    inside = [(s, e) for _, s, e in ops if t0 - ev.start_ns <= s]
    assert red["busy_s"] <= sum(e - s for s, e in inside) / 1e9
    assert dict(red["device_ops"])["jit__lambda/fusion"] == pytest.approx(
        211.912e-6)
    gaps = dict(red["idle_gaps"])
    assert set(gaps) == {"bench.sleep", "bench.scan"}
    assert sum(gaps.values()) == pytest.approx(
        red["window_s"] - red["busy_s"])


def test_chain_roofline_reads_the_chain_alone():
    """The refine chain's share divides by its own modules' time; the
    survivor takes' time moves it not, and without the chain it is silent."""
    from pathlib import Path

    from perfbench import harness

    reader = harness.load_module(Path(xtrace.__file__).parent / "metrics"
                                 / "refine_chain_roofline.scan.py")
    ctx = {"trace": {"busy_s": 2.5,
                     "module_busy_s": {"jit_fn": 0.5, "jit__lambda": 2.0}},
           "chain_bytes": int(0.1 * 819e9),
           "peak": xtrace.peak("TPU v5 lite")}
    assert reader.read(ctx) == pytest.approx(20.0)
    ctx["trace"]["module_busy_s"]["jit__lambda"] = 9.0
    assert reader.read(ctx) == pytest.approx(20.0)
    ctx["trace"]["module_busy_s"] = {"jit__lambda": 2.0}
    assert reader.read(ctx) is None
