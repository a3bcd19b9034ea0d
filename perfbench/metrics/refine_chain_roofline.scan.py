"""The scans' refine chain (the ``jit_fn`` launches: fused FP-delta decode
-> segmented min/max -> bbox mask, or the decode alone where a stream needs
no refine) against its roofline, in %: the chain's
necessary bytes (``nbytes.chain_bytes``) over the chip's HBM bandwidth,
divided by the device time of the chain's own operations in the window.
The survivor takes (``jit__lambda``) are neither in the bytes nor in the
time. The chain does compares and integer shifts, no floating-point
arithmetic to speak of, so bytes set the bound."""

CHAIN_MODULE = "jit_fn"


def read(ctx):
    busy = ctx["trace"]["module_busy_s"].get(CHAIN_MODULE, 0.0)
    if busy <= 0 or not ctx["chain_bytes"]:
        return None
    return 100.0 * ctx["chain_bytes"] / ctx["peak"]["hbm_bytes_per_s"] / busy
