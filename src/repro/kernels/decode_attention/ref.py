"""Pure-jnp oracle for decode attention (kv_len may be traced)."""

from __future__ import annotations

import math
from typing import Optional, Union

import jax
import jax.numpy as jnp


def decode_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                         kv_len: Optional[Union[int, jax.Array]] = None,
                         window: int = 0, softcap: float = 0.0,
                         scale: Optional[float] = None) -> jax.Array:
    """q (B, Hq, D); k/v (B, Hkv, S, D) → (B, Hq, D).  ``kv_len``: a
    scalar shared by every row, or (B,) — row ``b`` attends to
    ``[0, kv_len[b])``."""
    B, Hq, D = q.shape
    _, Hkv, S, _ = k.shape
    G = Hq // Hkv
    kv_len = S if kv_len is None else kv_len
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    # big tensors (k, v) stay in their storage dtype; the MXU accumulates
    # in fp32 via preferred_element_type — no materialized fp32 cache copy
    qf = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bhkd->bhgk", qf, k,
                   preferred_element_type=jnp.float32) * scale
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    cols = jnp.arange(S)
    lens = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (B,))[:, None]
    mask = cols < lens                                  # (B, S)
    if window > 0:
        mask = mask & (cols >= lens - window)
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bhkd->bhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Hq, D).astype(q.dtype)
