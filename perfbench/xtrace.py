"""Reduction of a JAX profiler trace to the benchmark's device numbers, and
the table of peaks it divides by.

Device time is read from the ``XLA Ops`` line of every ``/device:TPU:<n>``
plane, each op named by the ``XLA Modules`` event around it; host
annotations are the benchmark's own ``bench.*`` ``TraceAnnotation`` spans on
the host plane. All times are taken inside the
measured window, given in the profiler's clock (nanoseconds since the
trace's start).
"""

from __future__ import annotations

import bisect
import glob
import json
import os
from dataclasses import dataclass, field
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION_PREFIX = "bench."


def peak(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; unknown kinds are an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {PEAKS_FILE.name}")
    return table[device_kind]


@dataclass
class TraceEvents:
    """What the reduction reads from one trace: device op events per chip
    and host spans (the benchmark's annotations, to which the harness adds
    the program's obs spans), as (name, start_ns, end_ns)."""

    device: dict = field(default_factory=dict)   # plane name -> events
    host: list = field(default_factory=list)
    start_ns: int = 0                            # profiler start, epoch ns


def load(log_dir: str) -> TraceEvents:
    """Read the ``.xplane.pb`` that ``jax.profiler`` wrote under
    ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(paths)}")
    return from_profile(ProfileData.from_file(paths[0]))


def from_profile(pd) -> TraceEvents:
    out = TraceEvents()
    for plane in pd.planes:
        if plane.name == "Task Environment":
            for k, v in plane.stats:
                if k == "profile_start_time":
                    out.start_ns = int(v)
        elif plane.name.startswith("/device:TPU:") and plane.name[12:].isdigit():
            lines = {line.name: line for line in plane.lines}
            out.device[plane.name] = (_op_events(lines[OPS_LINE],
                                                 lines.get(MODULES_LINE))
                                      if OPS_LINE in lines else [])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        out.host.append((e.name, int(e.start_ns),
                                         int(e.start_ns + e.duration_ns)))
    return out


def _op_events(ops, modules) -> list[tuple[str, int, int]]:
    """Op events named ``<module>/<instruction>``: the trace names an op by
    its whole HLO text; the instruction is what precedes `` = ``, and the
    module (``jit_fn``, ``jit__lambda``, without its fingerprint) is the
    ``XLA Modules`` event that contains the op's start."""
    mods = []
    if modules is not None:
        mods = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                       e.name.split("(")[0]) for e in modules.events)
    starts = [m[0] for m in mods]
    out = []
    for e in ops.events:
        s = int(e.start_ns)
        instr = e.name.split(" = ")[0].lstrip("%")
        k = bisect.bisect_right(starts, s) - 1
        mod = mods[k][2] if k >= 0 and s < mods[k][1] else "(no module)"
        out.append((f"{mod}/{instr}", s, int(s + e.duration_ns)))
    return out


def _union(intervals) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _name_gaps(gaps, host) -> dict[str, int]:
    """Idle nanoseconds by the shortest host span open at each gap's middle
    (``"(none)"`` where none is); one sweep over both in time order."""
    host = sorted((s, e, n) for n, s, e in host)
    out: dict[str, int] = {}
    active: list[tuple[int, int, str]] = []
    k = 0
    for gs, ge in sorted(gaps):
        mid = (gs + ge) // 2
        while k < len(host) and host[k][0] <= mid:
            active.append(host[k])
            k += 1
        active = [h for h in active if h[1] > mid]
        name = min(active, key=lambda h: h[1] - h[0])[2] if active else "(none)"
        out[name] = out.get(name, 0) + (ge - gs)
    return out


def reduce(ev: TraceEvents, t0_ns: int, t1_ns: int, top: int = 10) -> dict:
    """Busy and idle time of the window [t0_ns, t1_ns) (trace clock).

    ``busy_s`` is the union of op intervals, averaged over the chips that
    ran any op, and ``module_busy_s`` the same for the ops of each module
    (``jit_fn``, ...) apart; ``device_ops`` the ops that took most time (summed over
    chips); ``idle_gaps`` the window's idle time on the first busy chip,
    summed by the shortest host span (``ev.host``) open at each gap's
    middle.
    """
    window = t1_ns - t0_ns
    if window <= 0:
        raise ValueError("empty trace window")
    busy_per_chip = []
    mod_busy: dict[str, int] = {}
    op_time: dict[str, int] = {}
    gaps_by: dict[str, int] = {}
    first = True
    for plane in sorted(ev.device):
        clipped = [(n, max(s, t0_ns), min(e, t1_ns))
                   for n, s, e in ev.device[plane] if e > t0_ns and s < t1_ns]
        if not clipped:
            continue
        for n, s, e in clipped:
            op_time[n] = op_time.get(n, 0) + (e - s)
        merged = _union((s, e) for _, s, e in clipped)
        busy_per_chip.append(sum(e - s for s, e in merged))
        by_mod: dict[str, list] = {}
        for n, s, e in clipped:
            by_mod.setdefault(n.split("/")[0], []).append((s, e))
        for mod, iv in by_mod.items():
            mod_busy[mod] = mod_busy.get(mod, 0) + sum(
                e - s for s, e in _union(iv))
        if first:
            first = False
            edges = [t0_ns] + [x for se in merged for x in se] + [t1_ns]
            gaps_by = _name_gaps([(gs, ge) for gs, ge in
                                  zip(edges[0::2], edges[1::2]) if ge > gs],
                                 ev.host)
    if not busy_per_chip:
        return {"busy_s": 0.0, "window_s": window / 1e9, "module_busy_s": {},
                "device_ops": [], "idle_gaps": []}
    busy = sum(busy_per_chip) / len(busy_per_chip)
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(gaps_by.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy / 1e9,
        "window_s": window / 1e9,
        "module_busy_s": {m: t / 1e9 / len(busy_per_chip)
                          for m, t in mod_busy.items()},
        "device_ops": [[n, t / 1e9] for n, t in ops],
        "idle_gaps": [[n, t / 1e9] for n, t in gaps],
    }
