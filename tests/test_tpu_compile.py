"""The main path's Pallas kernels, compiled for a described TPU v5e chip.

Nothing runs: each test lowers a kernel at its real tile and shapes and
compiles it with the TPU compiler for a ``v5e:2x2`` topology that is
described, not attached.  That catches what interpret mode cannot — an
index the chip's tiling cannot prove aligned, a dtype Mosaic cannot
lower, a block that overflows scoped VMEM — and asserts the compiled
program holds the kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the fixture keeps
that to the one test worker that runs this file.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.event_sweep import event_sweep

#: the largest capacity bucket chip_smoke.py's Figure-2 sweep dispatches
#: (MTBF 30 min over a 72-hour job).
FIG2_MAX_BUCKET = 4096

#: the largest float32 leaf of the xlstm-125m trainer state: the
#: embedding, vocab 50304 padded to a multiple of 256, by d_model 768.
XLSTM_125M_EMBED = (50432, 768)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("compensated", [True, False],
                         ids=["compensated_f32", "plain_f32"])
@pytest.mark.parametrize("F", [1024, FIG2_MAX_BUCKET])
def test_event_sweep_compiles(one_chip, F, compensated):
    """The event kernel at the engine's tile (8 x 128 lanes), a chunk of
    32 grid points x 1024 trials, under the engine's x64 context."""
    B, N = 32, 1024
    fn = functools.partial(event_sweep, n_steps=F + 1, dtype="float32",
                           compensated=compensated, force_interpret=False)
    with jax.enable_x64(True):
        col = _sds((B,), jnp.float64, one_chip)
        compiled = jax.jit(fn).lower(
            *[col] * 6, _sds((B, N, F), jnp.float64, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_event_sweep_refuses_f64_on_tpu():
    """Mosaic has no f64: the lowering path says so before compiling."""
    g = np.ones((1, 8, 4))
    col = np.ones(1)
    with jax.enable_x64(True), pytest.raises(ValueError, match="no f64"):
        event_sweep(col, col, col, col, col * 0.5, col * 10, g, n_steps=5,
                    dtype="float64", force_interpret=False)


def test_quantize_compiles(one_chip):
    x = _sds(XLSTM_125M_EMBED, jnp.float32, one_chip)
    compiled = jax.jit(
        lambda a: ops.quantize_array(a, force_interpret=False)[:2]
    ).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_dequantize_compiles(one_chip):
    size = int(np.prod(XLSTM_125M_EMBED))
    pad, D = ops._pad_of(size)
    rows = (size + pad) // D
    q = _sds((rows, D), jnp.int8, one_chip)
    s = _sds((rows, D // 128), jnp.float32, one_chip)
    compiled = jax.jit(functools.partial(
        ops.dequantize_array, shape=XLSTM_125M_EMBED, dtype="float32",
        pad=pad, force_interpret=False)).lower(q, s).compile()
    assert "tpu_custom_call" in compiled.as_text()
