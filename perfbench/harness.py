"""One run of one benchmark cell: build the lake from the seed, warm up, run
the cell's traffic for the window, check every answer against the plain
reference, and return the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by name: ``configs/<config>.json`` with its generator
``configs/<config>.py``, ``traffic/<traffic>.json`` with the loop of its
``kind`` in ``loops/<kind>.py``, and one reader ``metrics/<metric>.py`` per
per-layer metric.
"""

from __future__ import annotations

import importlib.util
import json
import os
import resource
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from . import lake, reference, xtrace

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def log(msg: str) -> None:
    print(f"[perfbench] {msg}", file=sys.stderr, flush=True)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "perfbench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """A workload of ``BENCHMARK.json`` with everything it names."""

    def __init__(self, workload: str, root: Path = ROOT):
        bench = json.loads((root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
        self.spec = cells[workload]
        self.name = workload
        entry = {c["name"]: c for c in bench["configs"]}[self.spec["config"]]
        cfg_file = root / entry["file"]
        self.cfg = json.loads(cfg_file.read_text())
        self.generator = load_module(cfg_file.with_suffix(".py"))
        self.mix = json.loads(
            (BENCH_DIR / "traffic" / f"{self.spec['traffic']}.json").read_text())
        self.loop = load_module(
            BENCH_DIR / "loops" / f"{self.mix['kind']}.py").Loop
        self.end_to_end = [m for m in bench["end_to_end"]
                           if workload in m.get("workloads", [workload])]
        e2e = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if (workload in m["workloads"] if "workloads" in m
                              else m["moves"] in e2e)]


class CompileLog:
    """Backend compiles (or persistent-cache loads) of every program the
    process builds, from JAX's monitoring events. The listener is
    process-wide, so one instance serves every run in a process."""

    _instance = None

    def __init__(self):
        import jax

        self.programs = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    @classmethod
    def get(cls) -> "CompileLog":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += secs


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def percentile(values, q: float) -> float:
    """The q-th percentile of raw samples (numpy's linear interpolation)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def device_info(chips: int) -> dict:
    """The devices JAX reports; fails unless they are at least ``chips``
    TPU chips."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"devices: {info}")
    if info["platform"] != "tpu":
        raise SystemExit(f"[perfbench] no TPU: JAX's default device is "
                         f"{info['platform']!r}")
    if info["count"] < chips:
        raise SystemExit(f"[perfbench] the cell needs {chips} chips, JAX "
                         f"finds {info['count']}")
    return info


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, device: dict) -> dict:
    """One run of ``cell``; returns the result line as a dict."""
    import jax

    from repro import obs
    from repro.dataset import SpatialDatasetScanner
    from repro.kernels import enable_compile_cache

    if seed < 0:
        raise ValueError("--seed must be a non-negative whole number")
    cache_dir = enable_compile_cache()
    compiles = CompileLog.get()
    cfg = cell.cfg
    t = time.perf_counter()
    data = cell.generator.generate(cfg, seed)
    ref = reference.Records(data, cfg)
    n_points = ref.n_values
    t_gen = time.perf_counter() - t
    with tempfile.TemporaryDirectory(prefix="perfbench_lake_") as root:
        t = time.perf_counter()
        lake.write_lake(root, cfg, data)
        t_write = time.perf_counter() - t
        t = time.perf_counter()
        scanner = SpatialDatasetScanner(root, on_error="raise")
        stored = lake.stored_bytes(scanner)
        pages = lake.page_table(scanner, cfg["filter"]["column"])
        loop = cell.loop(cell, ref, scanner, seed, seconds)
        c0, s0 = compiles.programs, compiles.seconds
        loop.warm()
        t_warm = time.perf_counter() - t
        setup_s = time.perf_counter() - t_start
        log(f"set-up {setup_s} s: generate {t_gen} s, write {t_write} s, "
            f"open + warm-up {t_warm} s ({compiles.programs - c0} programs "
            f"compiled or loaded in {compiles.seconds - s0} s; cache "
            f"{cache_dir}); lake {n_points} points, {stored} bytes")

        trace_dir = os.path.join(root, "trace")
        if trace:
            obs.enable()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        c1 = compiles.programs
        w0 = time.time_ns()
        perf_to_wall = w0 - time.perf_counter_ns()
        out = loop.window(seconds)
        w1 = time.time_ns()
        in_window = compiles.programs - c1
        if trace:
            jax.profiler.stop_trace()
            obs.disable()
        log(f"window {out['elapsed_s']} s, {len(out['latencies_s'])} "
            f"requests, {out['failed']} failed, {in_window} programs "
            "compiled or loaded inside it")
        stats = jax.devices()[0].memory_stats() or {}
        device = dict(device, memory_peak_bytes=int(
            stats.get("peak_bytes_in_use", 0)))

        if not trace:
            values = loop.e2e(out)
            values["setup_s"] = setup_s
            values["stored_bytes_per_point"] = stored / n_points
            metrics = {m["name"]: {"value": values[m["name"]],
                                   "unit": m["unit"]}
                       for m in cell.end_to_end}
            breakdown = None
        else:
            t = time.perf_counter()
            ev = xtrace.load(trace_dir)
            tracer = obs.get_tracer()
            # the program's spans, onto the profiler's clock
            shift = tracer.epoch_ns + perf_to_wall - ev.start_ns
            ev.host += [(e["name"], int(e["ts"] * 1e3) + shift,
                         int((e["ts"] + e["dur"]) * 1e3) + shift)
                        for e in tracer.spans()]
            red = xtrace.reduce(ev, w0 - ev.start_ns, w1 - ev.start_ns)
            log(f"trace reduction took {time.perf_counter() - t} s")
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
            ctx = {
                "n_requests": len(out["latencies_s"]),
                "spans": _span_seconds(tracer),
                "counters": obs.snapshot()["counters"],
                "trace": red,
                "peak": xtrace.peak(device["kind"]),
            }
            ctx.update(loop.layer_inputs(out, pages))
            metrics = {}
            for m in cell.per_layer:
                v = load_module(BENCH_DIR / "metrics" / f"{m['name']}.py"
                                ).read(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        loop.close()
        scanner.close()
        t = time.perf_counter()
        totals, n_checked = loop.check(out, ref)
        log(f"reference check of {n_checked} answers took "
            f"{time.perf_counter() - t} s; {time.perf_counter() - t_start} s "
            "since start; host peak RSS "
            f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss} KiB")
    checks = {k: {"value": v, "limit": 0} for k, v in totals.items()}
    correct = n_checked > 0 and all(v == 0 for v in totals.values())
    line = {"correct": correct, "attempted": len(out["latencies_s"]),
            "failed": out["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return line


def _span_seconds(tracer) -> dict:
    spans: dict[str, list] = {}
    for e in tracer.spans():
        spans.setdefault(e["name"], []).append(e["dur"] / 1e6)
    return spans
