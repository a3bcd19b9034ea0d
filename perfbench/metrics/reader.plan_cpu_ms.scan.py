"""CPU time of the threads that ran ``rg.plan`` (the span's thread CPU
time, counter ``cpu_ns.rg.plan``) per scan in the window, in ms. Against
``reader.plan_ms.scan`` (the same spans' wall time) it says how much of the
planning was Python running and how much its thread waited (GIL, I/O)."""


def read(ctx):
    n = ctx["n_requests"]
    ns = ctx["counters"].get("cpu_ns.rg.plan")
    return ns / 1e6 / n if n and ns is not None else None
