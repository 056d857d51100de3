"""Pallas TPU kernel: fused flash attention with GQA / SWA / softcap.

The attention working set is the framework's dominant HBM traffic; this
kernel is the transport layer + in-stream accelerator story applied to the
score computation: KV tiles stream HBM→VMEM (read manager) while the MXU
consumes them; the online-softmax state (m, l, acc) lives in VMEM scratch —
the dataflow element; nothing but the final O tile is ever written back.

Features (union of the assigned architectures' needs):
  * grouped-query attention (q heads : kv heads = G : 1),
  * causal masking,
  * sliding-window attention (Mixtral window 4096, gemma2 local 4096,
    hymba SWA 1024),
  * logit soft-capping (gemma2: tanh cap 50.0 on attention logits),
  * fp32 online softmax at any input dtype.

Block-sparsity: fully-masked (q, kv) tiles are skipped *before* the MXU
sees them (causal upper triangle; outside-window bands).  The skip is a
`pl.when` on block indices — the Pallas pipeline still prefetches the
block, which on TPU costs bandwidth but not MXU time; the hillclimb notes
in EXPERIMENTS.md quantify this and the XLA path's scan applies the same
structure.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 512
DEFAULT_BK = 512
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int,
                  softcap: float, bq: int, bk: int, n_k: int, seq_k: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * bq
    k_start = ik * bk

    # Block-level relevance: skip tiles that are fully masked.
    live = jnp.bool_(True)
    if causal:
        live = jnp.logical_and(live, k_start <= q_start + bq - 1)
    if window > 0:
        # highest kv index of this tile must reach the window's lower edge
        live = jnp.logical_and(live, k_start + bk - 1 >= q_start - window + 1)

    @pl.when(live)
    def _block():
        q = q_ref[0].astype(jnp.float32)         # (bq, d)
        k = k_ref[0].astype(jnp.float32)         # (bk, d)
        v = v_ref[0].astype(jnp.float32)         # (bk, d)
        if seq_k % bk:
            # rows past seq_k in the last kv tile are stale VMEM: zero
            # them so that p == 0 there cannot meet a NaN
            kv_rows = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (bk, 1), 0)
            v = jnp.where(kv_rows < seq_k, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)

        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = cols < seq_k                       # ragged tail
        if causal:
            mask = jnp.logical_and(mask, cols <= rows)
        if window > 0:
            mask = jnp.logical_and(mask, cols > rows - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                       # (bq, 1)
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
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           causal: bool = True, window: int = 0,
                           softcap: float = 0.0,
                           scale: Optional[float] = None,
                           block_q: int = DEFAULT_BQ,
                           block_k: int = DEFAULT_BK,
                           interpret: bool = False) -> jax.Array:
    """q (B, Hq, Sq, D), k/v (B, Hkv, Sk, D) → (B, Hq, Sq, D)."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} not a multiple of Hkv={Hkv}")
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    grid = (B * Hq, pl.cdiv(Sq, bq), pl.cdiv(Sk, bk))

    qr = q.reshape(B * Hq, Sq, D)
    kr = k.reshape(B * Hkv, Sk, D)
    vr = v.reshape(B * Hkv, Sk, D)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, bq=bq, bk=bk, n_k=grid[2], seq_k=Sk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, iq, ik: (bh // G, ik, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, iq, ik: (bh // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, Hq, Sq, D)
