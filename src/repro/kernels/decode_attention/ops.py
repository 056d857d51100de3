"""jit'd public wrapper for decode attention."""

from __future__ import annotations

import functools
from typing import Optional, Union

import jax

from . import decode_attention as da, ref


@functools.partial(jax.jit, static_argnames=(
    "window", "softcap", "scale", "block_k", "backend", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     kv_len: Optional[Union[int, jax.Array]] = None,
                     window: int = 0, softcap: float = 0.0,
                     scale: Optional[float] = None,
                     block_k: int = da.DEFAULT_BK,
                     backend: str = "pallas",
                     interpret: bool = False) -> jax.Array:
    if backend == "xla":
        return ref.decode_attention_ref(q, k, v, kv_len=kv_len,
                                        window=window, softcap=softcap,
                                        scale=scale)
    return da.decode_attention_pallas(
        q, k, v, kv_len=kv_len, window=window, softcap=softcap, scale=scale,
        block_k=block_k, interpret=interpret)
