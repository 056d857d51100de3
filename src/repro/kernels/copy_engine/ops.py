"""jit'd public wrappers for the copy engine, with backend dispatch.

`backend="pallas"` (the default) compiles the TPU kernel; `interpret=True`
runs it in Pallas interpret mode instead.  `backend="xla"` uses the jnp
oracle — semantically identical (tested).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax

from . import copy_engine, ref


@functools.partial(jax.jit, static_argnames=("transform", "out_dtype",
                                             "backend", "interpret"))
def copy_2d(x: jax.Array, transform: Optional[Callable] = None,
            out_dtype=None, backend: str = "pallas",
            interpret: bool = False) -> jax.Array:
    if backend == "xla":
        return ref.copy_2d_ref(x, transform, out_dtype)
    return copy_engine.copy_2d_pallas(
        x, transform=transform, out_dtype=out_dtype, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("backend", "interpret"))
def strided_copy_nd(x: jax.Array, backend: str = "pallas",
                    interpret: bool = False) -> jax.Array:
    if backend == "xla":
        return ref.strided_copy_nd_ref(x)
    return copy_engine.strided_copy_nd_pallas(x, interpret=interpret)
