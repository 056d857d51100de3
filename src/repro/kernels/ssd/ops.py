"""jit'd public wrapper for the SSD scan."""

from __future__ import annotations

import functools

import jax

from . import ssd as ssd_kernel, ref


@functools.partial(jax.jit, static_argnames=("chunk", "return_state",
                                             "backend", "interpret"))
def ssd(x: jax.Array, dt: jax.Array, A: jax.Array, D: jax.Array,
        B: jax.Array, C: jax.Array, chunk: int = 128,
        return_state: bool = False, backend: str = "pallas",
        interpret: bool = False):
    if backend == "xla":
        return ref.ssd_chunked_ref(x, dt, A, D, B, C, chunk=chunk,
                                   return_state=return_state)
    return ssd_kernel.ssd_pallas(x, dt, A, D, B, C, chunk=chunk,
                                 return_state=return_state,
                                 interpret=interpret)
