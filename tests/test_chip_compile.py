"""Compile the main-path Pallas kernels for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler is installed with JAX and
compiles for a topology that is described, not attached.  Each test
lowers one kernel at the widths the served models use and compiles it,
so a block shape, a cast or a VMEM budget that Mosaic refuses fails here
and not on the chip.  Interpret mode, which the kernel-vs-oracle tests
use, checks none of this.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.copy_engine.copy_engine import (copy_2d_pallas,
                                                   strided_copy_nd_pallas)
from repro.kernels.decode_attention.decode_attention import \
    decode_attention_pallas
from repro.kernels.flash_attention.flash_attention import \
    flash_attention_pallas
from repro.kernels.init_engine.init_engine import (iota_fill_pallas,
                                                   memset_pallas,
                                                   prng_fill_pallas)
from repro.kernels.matmul_dma.matmul_dma import matmul_pallas
from repro.kernels.ssd.ssd import ssd_pallas

# gemma2-2b attention: 8 query heads over 4 KV heads of width 256
HQ, HKV, DH = 8, 4, 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # libtpu would otherwise log to a directory of its own choosing
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    """Lower `fn` for the described chip, compile it, and check that the
    Pallas kernel is in the program."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    if args:
        compiled = jax.jit(fn).lower(*args).compile()
    else:
        compiled = jax.jit(fn, out_shardings=one_chip).lower().compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("S", [4096, 1032])
@pytest.mark.parametrize("window,softcap", [(0, 0.0), (4096, 50.0)],
                         ids=["global", "local"])
@pytest.mark.parametrize("kv_len_shape", [(), (8,)],
                         ids=["shared_len", "len_per_row"])
def test_decode_attention(one_chip, S, window, softcap, kv_len_shape):
    def fn(q, k, v, kv_len):
        return decode_attention_pallas(q, k, v, kv_len=kv_len, window=window,
                                       softcap=softcap, scale=DH ** -0.5)
    _compile(one_chip, fn, ((8, HQ, DH), jnp.bfloat16),
             ((8, HKV, S, DH), jnp.bfloat16), ((8, HKV, S, DH), jnp.bfloat16),
             (kv_len_shape, jnp.int32))


@pytest.mark.parametrize("Sq", [37, 512, 1000])
def test_flash_attention(one_chip, Sq):
    def fn(q, k, v):
        return flash_attention_pallas(q, k, v, causal=True, window=4096,
                                      softcap=50.0, scale=DH ** -0.5)
    _compile(one_chip, fn, ((1, HQ, Sq, DH), jnp.bfloat16),
             ((1, HKV, Sq, DH), jnp.bfloat16), ((1, HKV, Sq, DH), jnp.bfloat16))


@pytest.mark.parametrize("shape,dtype", [
    ((4096, 4096), jnp.bfloat16), ((4096, 4096), jnp.float32),
    ((1000, 300), jnp.float32)])
def test_copy_2d(one_chip, shape, dtype):
    _compile(one_chip, copy_2d_pallas, (shape, dtype))


def test_strided_copy_nd(one_chip):
    _compile(one_chip, strided_copy_nd_pallas, ((4, 2, 512, 1024), jnp.float32))


def test_memset(one_chip):
    _compile(one_chip, lambda: memset_pallas((4096, 4096), 0.0, jnp.bfloat16))


def test_iota_fill(one_chip):
    _compile(one_chip, lambda: iota_fill_pallas((4096, 4096), 7, jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8,
                                   jnp.uint32])
def test_prng_fill(one_chip, dtype):
    # float outputs used to need a uint32 -> float cast Mosaic refuses
    _compile(one_chip, lambda: prng_fill_pallas((4096, 4096), 11, dtype))


def test_matmul(one_chip):
    # gemma2-2b MLP up-projection for a 2048-token batch
    _compile(one_chip, matmul_pallas, ((2048, 2304), jnp.bfloat16),
             ((2304, 9216), jnp.bfloat16))


def test_ssd(one_chip):
    # mamba2-1.3b: 64 heads of width 64, state 128, one group, chunk 256
    H, S, P, N = 64, 2048, 64, 128

    def fn(x, dt, A, D, B, C):
        return ssd_pallas(x, dt, A, D, B, C, chunk=256, return_state=True)
    _compile(one_chip, fn, ((1, H, S, P), jnp.float32),
             ((1, H, S), jnp.float32), ((H,), jnp.float32),
             ((H,), jnp.float32), ((1, 1, S, N), jnp.float32),
             ((1, 1, S, N), jnp.float32))
