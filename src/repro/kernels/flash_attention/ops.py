"""User-facing attention op: GQA handling, padding, Pallas/ref dispatch."""

from __future__ import annotations

import jax.numpy as jnp

from .. import default_interpret
from . import kernel, ref


def attention(
    q: jnp.ndarray,   # (B, Hq, Sq, D)
    k: jnp.ndarray,   # (B, Hkv, Sk, D)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    use_pallas: bool = False,
    interpret: bool | None = None,
    block_q: int = 128,
    block_k: int = 128,
) -> jnp.ndarray:
    """Attention with GQA (Hq a multiple of Hkv: k/v broadcast per group).

    ``use_pallas=False`` (default on CPU) runs the jnp oracle — the dry-run /
    CPU-training path. ``use_pallas=True`` runs the Pallas kernel (interpret
    mode off-TPU).
    """
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0, (hq, hkv)
    if hq != hkv:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if not use_pallas:
        return ref.attention_ref(q, k, v, causal=causal, sm_scale=sm_scale)
    interp = default_interpret() if interpret is None else interpret
    # The kernel takes no mask input, so the key length must be block-aligned
    # (serving caches and training seq lens are). Queries are *front*-padded:
    # real query i lands on padded row i+pad, which preserves the causal
    # diagonal offset (c <= i + (Sk - Sq)) exactly.
    sk = k.shape[2]
    if sk % block_k:
        raise ValueError(f"pallas path needs Sk % block_k == 0, got {sk}")
    pad_q = (-sq) % block_q
    if pad_q:
        if not causal:
            raise ValueError("non-causal pallas path needs Sq % block_q == 0")
        q = jnp.pad(q, ((0, 0), (0, 0), (pad_q, 0), (0, 0)))
    out = kernel.flash_attention(
        q, k, v, causal=causal, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, interpret=interp,
    )
    return out[:, :, pad_q:]
