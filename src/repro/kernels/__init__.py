"""Pallas kernels of the lake's device path, and the process settings they share.

``fp_delta`` decodes FP-delta page streams and runs the fused
decode→bbox-refine chain; ``minmax`` computes page statistics and the
segmented per-record min/max; ``tile_scan`` is the in-tile segmented scan
both use. Two settings belong to the whole package:

* :func:`default_interpret` — kernels compile with Mosaic on a TPU and run
  in the Pallas interpreter on the CPU (tests, rehearsals). No other
  backend has a path.
* :func:`enable_compile_cache` — JAX's persistent compilation cache, which
  entry points turn on so a second run loads the compiled shape buckets of
  the ``fp_delta`` AOT cache instead of compiling them again.

Importing this package touches no JAX state.
"""

from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache: a fixed path (part of the cache key), git-ignored
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


class DeviceCompileError(RuntimeError):
    """A device program failed to compile (cause chained).

    Lowering refusals surface from JAX as ``ValueError`` or
    ``NotImplementedError``; this type keeps them apart from malformed
    input, which the dataset scanner's error policy may skip.
    """


def default_interpret() -> bool:
    """Pallas interpret mode for the default backend: off on a TPU, on for
    the CPU. Raises on any other backend rather than interpret silently."""
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"no Pallas path for the {backend!r} backend: kernels compile for "
        "'tpu' and run interpreted on 'cpu'")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is the directory and nothing
    else is set; otherwise the cache goes to :data:`DEFAULT_CACHE_DIR`.
    Every compile is persisted, however short: the kernels compile in one
    or two seconds each, under JAX's default one-second floor.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
