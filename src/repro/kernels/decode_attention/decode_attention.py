"""Pallas TPU kernel: single-token decode attention over a long KV cache.

The serving hot loop: one new query token per sequence attends to a KV
cache of up to 512 Ki tokens.  This is a *pure data-movement* problem —
arithmetic intensity ~1 flop/byte — i.e. exactly the regime the paper's
engine targets ('decoupling memory accesses from execution'): the KV
stream is issued tile-by-tile by the Pallas pipeline (read manager), and
the GQA group of q heads sharing each kv head is packed into the sublane
dimension so every fetched KV tile feeds G MXU rows.

Layout: q (B, Hq, D) with Hq = Hkv * G; kv (B, Hkv, S, D).
Grid: (B, Hkv, S / bk) — kv tiles stream sequentially per (batch, kv head),
online softmax state in VMEM scratch.

`kv_len` is **traced** (the current cache fill), so one compiled kernel
serves the whole decode session: a scalar shared by every row, or one
length per row (B,) when rows sit at different positions.  The lengths
live in SMEM and grid row ``b`` reads its own; the compute of tiles
beyond a row's fill is skipped via `pl.when` (their DMA is not).
`window` (sliding-window decode) and `softcap` are static features.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BK = 1024
NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *,
                   scale: float, window: int, softcap: float,
                   bk: int, n_k: int, G: int, seq_k: int):
    ik = pl.program_id(2)
    kv_len = len_ref[pl.program_id(0)]

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k_start = ik * bk
    live = k_start < kv_len
    if window > 0:
        live = jnp.logical_and(live, k_start + bk - 1 >= kv_len - window)

    @pl.when(live)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)          # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, D)
        if seq_k % bk:
            # the last tile runs past the cache: its rows beyond seq_k
            # hold whatever the VMEM buffer held, and 0 * NaN is NaN
            rows = k_start + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
            v = jnp.where(rows < seq_k, v, 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (G, bk), 1)
        mask = cols < kv_len
        if window > 0:
            mask = jnp.logical_and(mask, cols >= kv_len - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == n_k - 1)
    def _retire():
        l = l_ref[...]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def decode_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                            kv_len: Optional[Union[int, jax.Array]] = None,
                            window: int = 0, softcap: float = 0.0,
                            scale: Optional[float] = None,
                            block_k: int = DEFAULT_BK,
                            interpret: bool = False) -> jax.Array:
    """q (B, Hq, D); k/v (B, Hkv, S, D) → (B, Hq, D).  ``kv_len``: a
    scalar shared by every row, or (B,) — row ``b`` attends to
    ``[0, kv_len[b])``."""
    B, Hq, D = q.shape
    _, Hkv, S, _ = k.shape
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} not a multiple of Hkv={Hkv}")
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    kv_len = S if kv_len is None else kv_len
    len_arr = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (B,))
    bk = min(block_k, S)
    grid = (B, Hkv, pl.cdiv(S, bk))

    qr = q.reshape(B, Hkv, G, D)

    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, softcap=softcap,
        bk=bk, n_k=grid[2], G=G, seq_k=S)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, D), lambda b, h, ik: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ik: (b, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, ik: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((G, 1), jnp.float32),
                        pltpu.VMEM((G, 1), jnp.float32),
                        pltpu.VMEM((G, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(len_arr, qr, k, v)
    return out.reshape(B, Hq, D)
