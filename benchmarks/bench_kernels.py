"""Kernel-plane benchmarks (beyond-paper: the TPU adaptation).

* host FP-delta codec throughput (the paper's encoder, vectorized numpy),
* Pallas miniblock codec (interpret mode on CPU — correctness-plane numbers;
  real TPU timing comes from the roofline model),
* page-stream device decode of the paper-exact format (host plan + batched
  Pallas/jnp execution — the read path's ``device="jax"`` back half),
* miniblock size penalty vs the paper-exact n* stream (DESIGN.md §5 claims
  <~8% on GPS-like data),
* flash-attention kernel vs jnp oracle equivalence timing at small shape.
"""

from __future__ import annotations

import time

import numpy as np

from repro.core.fp_delta import (
    fp_delta_decode,
    fp_delta_encode,
    fp_delta_encode_pages,
    fp_delta_plan,
)
from repro.kernels import fp_delta as fpd

from .common import make_dataset


def _throughput(fn, *args, reps=3):
    fn(*args)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    return (time.perf_counter() - t0) / reps, out


def run(scale: float = 1.0) -> list[dict]:
    rows = []
    cols = make_dataset("PT", scale)
    x64 = np.ascontiguousarray(cols.x)
    x32 = x64.astype(np.float32)

    s, (payload, st) = _throughput(lambda a: fp_delta_encode(a), x64)
    rows.append(dict(table="K", name="host_fp_delta64_encode",
                     mbps=x64.nbytes / s / 1e6, n=len(x64),
                     ratio=x64.nbytes / len(payload), n_star=st.n_bits))
    s, _ = _throughput(lambda p: fp_delta_decode(p, len(x64), np.float64), payload)
    rows.append(dict(table="K", name="host_fp_delta64_decode",
                     mbps=x64.nbytes / s / 1e6, n=len(x64)))

    # page-stream device decode: host escape resolution + one batched launch
    n_pages = 8
    bounds = [(i * len(x64) // n_pages, (i + 1) * len(x64) // n_pages)
              for i in range(n_pages)]
    plans = [fp_delta_plan(payload, v1 - v0, np.float64)
             for (payload, _), (v0, v1) in zip(
                 fp_delta_encode_pages(x64, bounds), bounds)]
    def stream_decode(use_pallas):
        return fpd.decode_page_stream(fpd.build_page_stream(plans),
                                      use_pallas=use_pallas)

    s, _ = _throughput(stream_decode, True)
    rows.append(dict(table="K", name="stream_decode64_interpret",
                     mbps=x64.nbytes / s / 1e6, n=len(x64), pages=n_pages))
    s, _ = _throughput(stream_decode, False)
    rows.append(dict(table="K", name="stream_decode64_ref",
                     mbps=x64.nbytes / s / 1e6, n=len(x64), pages=n_pages))

    p32, st32 = fp_delta_encode(x32)
    stream = fpd.encode(x32, use_pallas=False)
    mini_bytes = stream.compact_bits() / 8
    rows.append(dict(table="K", name="miniblock_vs_exact_penalty",
                     exact_bytes=len(p32), mini_bytes=int(mini_bytes),
                     penalty_pct=100.0 * (mini_bytes / len(p32) - 1.0)))

    n = min(len(x32), 64 * 1024)
    xs = x32[:n]
    s, _ = _throughput(lambda a: fpd.encode(a, use_pallas=True), xs)
    rows.append(dict(table="K", name="pallas_encode_interpret", mbps=xs.nbytes / s / 1e6, n=n))
    st2 = fpd.encode(xs, use_pallas=True)
    s, _ = _throughput(lambda st_: fpd.decode(st_, use_pallas=True), st2)
    rows.append(dict(table="K", name="pallas_decode_interpret", mbps=xs.nbytes / s / 1e6, n=n))

    # flash attention oracle-vs-kernel micro check
    import jax, jax.numpy as jnp
    from repro.kernels.flash_attention import attention
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(0, 1, (1, 4, 256, 64)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (1, 4, 256, 64)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (1, 4, 256, 64)).astype(np.float32))
    o_ref = attention(q, k, v, causal=True, use_pallas=False)
    o_pal = attention(q, k, v, causal=True, use_pallas=True)
    err = float(jnp.max(jnp.abs(o_ref - o_pal)))
    rows.append(dict(table="K", name="flash_attention_maxerr", err=err))
    return rows


def summarize(rows) -> list[str]:
    out = ["# Kernel plane"]
    for r in rows:
        extras = {k: v for k, v in r.items() if k not in ("table", "name")}
        out.append(f"K {r['name']}: " + " ".join(f"{k}={v:.3g}" if isinstance(v, float) else f"{k}={v}" for k, v in extras.items()))
    return out
