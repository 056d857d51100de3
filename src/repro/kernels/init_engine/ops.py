"""jit'd public wrappers for the Init engine."""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from . import init_engine, ref


@functools.partial(jax.jit, static_argnames=("shape", "value", "dtype",
                                             "backend", "interpret"))
def memset(shape: Tuple[int, int], value=0.0, dtype=jnp.float32,
           backend: str = "pallas",
           interpret: bool = False) -> jax.Array:
    if backend == "xla":
        return ref.memset_ref(shape, value, dtype)
    return init_engine.memset_pallas(shape, value, dtype,
                                     interpret)


@functools.partial(jax.jit, static_argnames=("shape", "start", "dtype",
                                             "backend", "interpret"))
def iota_fill(shape: Tuple[int, int], start: int = 0, dtype=jnp.int32,
              backend: str = "pallas",
              interpret: bool = False) -> jax.Array:
    if backend == "xla":
        return ref.iota_fill_ref(shape, start, dtype)
    return init_engine.iota_fill_pallas(shape, start, dtype,
                                        interpret)


@functools.partial(jax.jit, static_argnames=("shape", "seed", "dtype",
                                             "backend", "interpret"))
def prng_fill(shape: Tuple[int, int], seed: int = 0, dtype=jnp.float32,
              backend: str = "pallas",
              interpret: bool = False) -> jax.Array:
    if backend == "xla":
        return ref.prng_fill_ref(shape, seed, dtype)
    return init_engine.prng_fill_pallas(shape, seed, dtype,
                                        interpret)
