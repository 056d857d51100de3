"""jit'd public wrapper for flash attention."""

from __future__ import annotations

import functools
from typing import Optional

import jax

from . import flash_attention as fa, ref


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "block_q", "block_k",
    "backend", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale: Optional[float] = None,
                    block_q: int = fa.DEFAULT_BQ, block_k: int = fa.DEFAULT_BK,
                    backend: str = "pallas",
                    interpret: bool = False) -> jax.Array:
    if backend == "xla":
        return ref.attention_ref(q, k, v, causal=causal, window=window,
                                 softcap=softcap, scale=scale)
    return fa.flash_attention_pallas(
        q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
        block_q=block_q, block_k=block_k,
        interpret=interpret)
