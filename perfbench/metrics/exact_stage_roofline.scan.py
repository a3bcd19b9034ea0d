"""The exact stage (the ``jit_exact_fn`` launches: gather of the refine's
survivors' coordinates, order-key compares, per-record classes) against its
roofline, in %: the stage's necessary bytes (``nbytes_exact.exact_bytes``)
over the chip's HBM bandwidth, divided by the device time of the stage's
own operations in the window. It compares and counts, with no
floating-point arithmetic, so bytes set the bound."""

STAGE_MODULE = "jit_exact_fn"


def read(ctx):
    busy = ctx["trace"]["module_busy_s"].get(STAGE_MODULE, 0.0)
    nb = ctx.get("exact_bytes")
    if busy <= 0 or not nb:
        return None
    return 100.0 * nb / ctx["peak"]["hbm_bytes_per_s"] / busy
