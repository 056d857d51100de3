"""jit'd public wrapper for the DMA-pipelined matmul."""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax

from . import matmul_dma, ref


@functools.partial(jax.jit, static_argnames=("block", "out_dtype",
                                             "epilogue", "backend",
                                             "interpret"))
def matmul(x: jax.Array, w: jax.Array,
           block: Optional[Tuple[int, int, int]] = None,
           out_dtype=None, epilogue: Optional[Callable] = None,
           backend: str = "pallas",
           interpret: bool = False) -> jax.Array:
    if backend == "xla":
        return ref.matmul_ref(x, w, out_dtype, epilogue)
    return matmul_dma.matmul_pallas(
        x, w, block=block, out_dtype=out_dtype, epilogue=epilogue,
        interpret=interpret)
