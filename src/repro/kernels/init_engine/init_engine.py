"""Pallas TPU kernel: the Init pseudo-protocol (paper Table 3).

'The Init pseudo-protocol only provides a read manager emitting a
configurable stream of either the same repeated value, incrementing
values, or a pseudorandom sequence. This enables our engine to accelerate
memory initialization.'

On TPU this is a *generator* kernel: no HBM read traffic at all — the
write manager is the only memory client, so the kernel runs at pure write
bandwidth (the per-kernel roofline lists 0 read bytes).  The pseudorandom
stream is the same splitmix32 counter PRNG as the RTL-level functional
model (`repro.core.backend.splitmix32`) — one oracle for both fabrics.

Used by the framework for parameter-buffer zeroing, KV-cache page
initialization on allocation, and synthetic-data generation.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.backend import splitmix32
from repro.core.engine import plan_nd_copy


def _memset_kernel(o_ref, *, value):
    o_ref[...] = jnp.full(o_ref.shape, value, o_ref.dtype)


def _iota_kernel(o_ref, *, start, cols_total, tile):
    i = pl.program_id(0)
    j = pl.program_id(1)
    tr, tc = tile
    row = jax.lax.broadcasted_iota(jnp.int32, (tr, tc), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (tr, tc), 1)
    flat = (row + i * tr) * cols_total + (col + j * tc)
    o_ref[...] = (flat + start).astype(o_ref.dtype)


def _prng_kernel(o_ref, *, seed, cols_total, tile):
    i = pl.program_id(0)
    j = pl.program_id(1)
    tr, tc = tile
    row = jax.lax.broadcasted_iota(jnp.uint32, (tr, tc), 0)
    col = jax.lax.broadcasted_iota(jnp.uint32, (tr, tc), 1)
    ctr = (row + jnp.uint32(i * tr)) * jnp.uint32(cols_total) \
        + (col + jnp.uint32(j * tc))
    bits = splitmix32(ctr + jnp.uint32(seed))
    if o_ref.dtype == jnp.uint32:
        o_ref[...] = bits
    elif o_ref.dtype in (jnp.float32, jnp.bfloat16):
        # uniform [0, 1) from the top 24 bits.  They fit int32, so the
        # float conversion starts from int32: Mosaic has no uint32 ->
        # float cast.
        top = jax.lax.bitcast_convert_type(bits >> jnp.uint32(8), jnp.int32)
        o_ref[...] = (top.astype(jnp.float32) /
                      jnp.float32(1 << 24)).astype(o_ref.dtype)
    elif o_ref.dtype == jnp.int8:
        o_ref[...] = (bits & jnp.uint32(0xFF)).astype(jnp.uint8) \
            .view(jnp.int8).reshape(o_ref.shape)
    else:
        raise NotImplementedError(f"prng fill for {o_ref.dtype}")


def _launch(kernel, shape: Tuple[int, int], dtype, interpret: bool):
    plan = plan_nd_copy(shape, jnp.dtype(dtype).itemsize)
    tr, tc = plan.tile
    return pl.pallas_call(
        kernel,
        grid=plan.grid,
        out_specs=pl.BlockSpec((tr, tc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        interpret=interpret,
    )(), plan


def memset_pallas(shape: Tuple[int, int], value, dtype=jnp.float32,
                  interpret: bool = False) -> jax.Array:
    kern = functools.partial(_memset_kernel, value=value)
    out, _ = _launch(kern, shape, dtype, interpret)
    return out


def iota_fill_pallas(shape: Tuple[int, int], start: int = 0,
                     dtype=jnp.int32, interpret: bool = False) -> jax.Array:
    plan = plan_nd_copy(shape, jnp.dtype(dtype).itemsize)
    kern = functools.partial(_iota_kernel, start=start,
                             cols_total=shape[1], tile=plan.tile)
    tr, tc = plan.tile
    return pl.pallas_call(
        kern, grid=plan.grid,
        out_specs=pl.BlockSpec((tr, tc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        interpret=interpret)()


def prng_fill_pallas(shape: Tuple[int, int], seed: int = 0,
                     dtype=jnp.float32, interpret: bool = False) -> jax.Array:
    plan = plan_nd_copy(shape, jnp.dtype(dtype).itemsize)
    kern = functools.partial(_prng_kernel, seed=seed,
                             cols_total=shape[1], tile=plan.tile)
    tr, tc = plan.tile
    return pl.pallas_call(
        kern, grid=plan.grid,
        out_specs=pl.BlockSpec((tr, tc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        interpret=interpret)()
