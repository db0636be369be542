"""Compile the main-path Pallas kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler is installed and compiles for a topology
that is described, not attached.  Each kernel is compiled with
``interpret=False`` at the size its caller runs (3mm's 512x512 float32
matmul, tdFIR's 64 filters x 4096 samples x 128 taps, attention at
granite-3-2b's head width) and must come out as a Mosaic
``tpu_custom_call``.  What the chip's compiler refuses here — a block the
TPU tiling cannot take, a scalar store to VMEM — is refused before any chip
time is spent.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every xdist worker imports this
file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attention as dak
from repro.kernels import flash_attention as fa
from repro.kernels import matmul as mm
from repro.kernels import tdfir as fir


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: an executable compiled for a described chip can be written
    there but not read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _matmul():
    return (lambda a, b: mm.matmul(a, b, interpret=False),
            [((512, 512), jnp.float32), ((512, 512), jnp.float32)])


def _tdfir():
    return (lambda x, h: fir.tdfir(x, h, block_n=128, interpret=False),
            [((64, 4096), jnp.float32), ((64, 128), jnp.float32)])


def _flash_attention():
    # granite-3-2b: 32 heads of width 64, a 1024-token prompt
    return (lambda q, k, v: fa.flash_attention(q, k, v, interpret=False),
            [((32, 1024, 64), jnp.bfloat16)] * 3)


def _decode_attention():
    return (lambda q, k, v: dak.decode_attention(q, k, v, jnp.int32(700),
                                                 interpret=False),
            [((32, 64), jnp.bfloat16), ((32, 1024, 64), jnp.bfloat16),
             ((32, 1024, 64), jnp.bfloat16)])


@pytest.mark.parametrize("case", [_matmul, _tdfir, _flash_attention,
                                  _decode_attention],
                         ids=["matmul", "tdfir", "flash_attention",
                              "decode_attention"])
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = case()
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
