"""The lake's device kernels compile for a TPU v5e at real sizes.

Interpret mode on the CPU checks what the kernels compute; only the chip's
own compiler (Mosaic) says whether they lower at all — it refuses layouts
and gathers that the interpreter runs happily. These tests compile every
kernel of the device path, and the two fused decode→refine chains that the
scanner and the query server launch, for a *described* v5e chip with
``interpret=False``: nothing runs, so they need no chip and take a few
seconds each.

The topology is described inside a fixture, never while modules import:
only one process may load the TPU library, and pytest-xdist workers import
every test file.
"""

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.kernels.fp_delta import kernel as fpd_kernel  # noqa: E402
from repro.kernels.fp_delta import ops as fpd_ops  # noqa: E402
from repro.kernels.fp_delta.ref import STREAM_BLOCK  # noqa: E402
from repro.kernels.minmax import kernel as mm_kernel  # noqa: E402

# one launch at the per-launch cap: 2^26 payload bits = 2^21 int32 words
LAUNCH_WORDS = fpd_ops._MAX_LAUNCH_BITS // 32
# ~2 M values at ~30 bits each fill the cap: 2048 blocks of STREAM_BLOCK
N_BLOCKS = 2048
# PT-like trips hold ~48 points: ~21 K records per launch, pow2-bucketed
N_RECORDS = 1 << 15
N_QUERIES = 64        # one server wave of concurrent bbox queries
PAGE_VALUES = 8192    # the writer's page size in the chip run
N_PAGES = 2048        # column_page_stats' batch budget at that page size


@pytest.fixture(scope="module")
def one_chip():
    """A single device of a described v5e, with the persistent compile
    cache off: entries written for an absent chip cannot be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _compile(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


def _stream_shapes():
    blocks = ((N_BLOCKS, STREAM_BLOCK), jnp.int32)
    return [((LAUNCH_WORDS,), jnp.int32), blocks, blocks, blocks]


def test_decode_stream_kernel_compiles(one_chip):
    _compile(
        lambda w, o, n, a: fpd_kernel.decode_stream_limbs(
            w, o, n, a, interpret=False),
        _stream_shapes(), one_chip)


def test_segmented_minmax_kernel_compiles(one_chip):
    blocks = ((N_BLOCKS, STREAM_BLOCK), jnp.int32)
    _compile(
        lambda lo, hi, f: mm_kernel.segminmax_blocks(lo, hi, f, interpret=False),
        [blocks] * 3, one_chip)


def test_page_minmax_kernel_compiles(one_chip):
    _compile(lambda x: mm_kernel.minmax(x, interpret=False),
             [((N_PAGES, PAGE_VALUES), jnp.float32)], one_chip)


@pytest.mark.parametrize("width", [32, 64])
@pytest.mark.parametrize("chain", ["refine", "refine_multi"])
def test_fused_refine_chain_compiles(one_chip, chain, width):
    if chain == "refine":
        fn, qkeys = fpd_ops._refine_jit(width, True, False), (4, 2)
    else:
        fn, qkeys = fpd_ops._refine_multi_jit(width, True, False), (N_QUERIES, 4, 2)
    shapes = _stream_shapes() + [
        ((N_BLOCKS, STREAM_BLOCK), jnp.int32),   # seg_flag
        ((N_RECORDS, 2), jnp.int32),             # end_pos
        ((N_RECORDS,), jnp.bool_),               # valid
        (qkeys, jnp.uint32),
    ]
    _compile(fn, shapes, one_chip)


@pytest.mark.parametrize("width", [32, 64])
def test_exact_stage_compiles(one_chip, width):
    """The exact stage is plain XLA (gathers, compares, cumsums), under its
    own module name so the trace keeps it apart from the refine chain."""
    from repro.kernels import exact

    n_vals = 1 << 18   # the gathered values of a launch's kept records
    stream = ((N_BLOCKS * STREAM_BLOCK,), jnp.uint32)
    vals = ((n_vals,), jnp.int32)
    shapes = [stream, stream, vals, vals, vals, ((n_vals,), jnp.bool_),
              vals, vals, ((5, 2), jnp.uint32)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    lowered = exact._exact_jit(width).lower(*args)
    assert "jit_exact_fn" in lowered.as_text()
    lowered.compile()
