"""Grouped-query attention with SWA / softcap / partial RoPE — both
execution paths (Pallas kernels; chunked-flash pure-XLA) plus the decode
step against a KV cache.

The XLA path's `chunked_flash` is the same online-softmax tiling as the
Pallas kernel, expressed as `lax.scan` over KV chunks (so the 32 Ki-token
prefill never materializes an (S, S) score matrix) — this is the path the
512-device dry-run lowers.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, RunConfig
from repro.dist.sharding import hint
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from .common import Params, apply_rope, dense, dense_init, fold_keys

NEG_INF = -1e30


def init_attention(key, cfg: ArchConfig, cross: bool = False) -> Params:
    d = cfg.d_model
    dh = cfg.resolved_head_dim
    kq, kk, kv, ko = fold_keys(key, "wq", "wk", "wv", "wo")
    return {
        "wq": dense_init(kq, d, cfg.n_heads * dh, bias=cfg.qkv_bias),
        "wk": dense_init(kk, d, cfg.n_kv_heads * dh, bias=cfg.qkv_bias),
        "wv": dense_init(kv, d, cfg.n_kv_heads * dh, bias=cfg.qkv_bias),
        "wo": dense_init(ko, cfg.n_heads * dh, d,
                         stddev=1.0 / math.sqrt(cfg.n_heads * dh)),
    }


# --------------------------------------------------------------------------
# XLA-path chunked flash attention (lax.scan over KV tiles)
# --------------------------------------------------------------------------

def chunked_flash(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool, window: int, softcap_v: float,
                  scale: float, chunk_q: int, chunk_k: int) -> jax.Array:
    """q (B,Hq,Sq,D), k/v (B,Hkv,Sk,D) → (B,Hq,Sq,D); fp32 softmax."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    G = Hq // Hkv
    bq = min(chunk_q, Sq)
    bk = min(chunk_k, Sk)
    nq = -(-Sq // bq)
    nk = -(-Sk // bk)
    Sq_p, Sk_p = nq * bq, nk * bk

    # keep q/k/v in storage dtype; accumulate scores in fp32 on the MXU
    qf = q * jnp.asarray(scale, q.dtype)
    if Sq_p != Sq:
        qf = jnp.pad(qf, ((0, 0), (0, 0), (0, Sq_p - Sq), (0, 0)))
    kf = k
    vf = v
    if Sk_p != Sk:
        kf = jnp.pad(kf, ((0, 0), (0, 0), (0, Sk_p - Sk), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, 0), (0, Sk_p - Sk), (0, 0)))

    # (B, Hkv, G, nq, bq, D) — sharding hints keep attention parallel on
    # heads when they divide the model axis, on q-sequence blocks
    # (context parallelism) otherwise.
    qf = hint("attn_q6", qf.reshape(B, Hkv, G, nq, bq, D))
    kf = hint("attn_kv5", kf.reshape(B, Hkv, nk, bk, D))
    vf = hint("attn_kv5", vf.reshape(B, Hkv, nk, bk, D))

    rows = jnp.arange(Sq_p).reshape(nq, bq)                 # q positions

    def kv_step(carry, inp):
        m, l, acc = carry                                   # (B,Hkv,G,nq,bq[,D])
        kc, vc, jblk = inp                                  # (B,Hkv,bk,D), idx
        cols = jblk * bk + jnp.arange(bk)                   # (bk,)
        s = jnp.einsum("bhgqtd,bhkd->bhgqtk", qf, kc,
                       preferred_element_type=jnp.float32)
        if softcap_v > 0:
            s = softcap_v * jnp.tanh(s / softcap_v)
        mask = (cols[None, None, :] < Sk)
        if causal:
            mask = mask & (cols[None, None, :] <= rows[:, :, None])
        if window > 0:
            mask = mask & (cols[None, None, :] > rows[:, :, None] - window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + \
            jnp.einsum("bhgqtk,bhkd->bhgqtd", p.astype(vc.dtype), vc,
                       preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hkv, G, nq, bq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, nq, bq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, nq, bq, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        kv_step, (m0, l0, a0),
        (kf.transpose(2, 0, 1, 3, 4), vf.transpose(2, 0, 1, 3, 4),
         jnp.arange(nk)))
    denom = jnp.where(l == 0.0, 1.0, l)
    out = (acc / denom[..., None]).reshape(B, Hq, Sq_p, D)[:, :, :Sq]
    return hint("attn_out", out.astype(q.dtype))


def _attend(q, k, v, *, causal, window, softcap_v, scale, rcfg: RunConfig):
    if rcfg.kernels == "pallas":
        return flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap_v, scale=scale)
    return chunked_flash(q, k, v, causal, window, softcap_v, scale,
                         rcfg.attn_chunk_q, rcfg.attn_chunk_k)


# --------------------------------------------------------------------------
# Layer forward
# --------------------------------------------------------------------------

def _split_heads(x, n, dh):
    B, S, _ = x.shape
    return x.reshape(B, S, n, dh).transpose(0, 2, 1, 3)


def _merge_heads(x):
    B, H, S, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, S, H * D)


def attention_forward(p: Params, x: jax.Array, cfg: ArchConfig,
                      rcfg: RunConfig, *, window: int,
                      positions: Optional[jax.Array] = None,
                      causal: bool = True,
                      kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
                      return_kv: bool = False):
    """Full-sequence attention (train / prefill).

    `kv_override` — encoder outputs' (k, v) for cross-attention (no RoPE).
    `return_kv` — also return the roped (k, v) for the prefill→decode
    cache handoff.
    """
    B, S, _ = x.shape
    dh = cfg.resolved_head_dim
    compute = jnp.bfloat16 if rcfg.dtype == "bfloat16" else jnp.float32

    q = _split_heads(dense(p["wq"], x, compute), cfg.n_heads, dh)
    if kv_override is None:
        k = _split_heads(dense(p["wk"], x, compute), cfg.n_kv_heads, dh)
        v = _split_heads(dense(p["wv"], x, compute), cfg.n_kv_heads, dh)
        if positions is None:
            positions = jnp.arange(S)
        q = apply_rope(q, positions, cfg.rope_fraction, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_fraction, cfg.rope_theta)
    else:
        k, v = kv_override

    scale = cfg.query_scale if cfg.query_scale is not None \
        else 1.0 / math.sqrt(dh)
    o = _attend(q, k, v, causal=causal and kv_override is None,
                window=window, softcap_v=cfg.attn_softcap, scale=scale,
                rcfg=rcfg)
    out = dense(p["wo"], _merge_heads(o), compute)
    if return_kv:
        return out, (k, v)
    return out


def cross_kv(p: Params, enc_out: jax.Array, cfg: ArchConfig,
             rcfg: RunConfig) -> Tuple[jax.Array, jax.Array]:
    """Precompute cross-attention K/V from encoder output."""
    compute = jnp.bfloat16 if rcfg.dtype == "bfloat16" else jnp.float32
    dh = cfg.resolved_head_dim
    k = _split_heads(dense(p["wk"], enc_out, compute), cfg.n_kv_heads, dh)
    v = _split_heads(dense(p["wv"], enc_out, compute), cfg.n_kv_heads, dh)
    return k, v


def attention_decode_step(p: Params, x: jax.Array, cache_k: jax.Array,
                          cache_v: jax.Array, pos: jax.Array,
                          cfg: ArchConfig, rcfg: RunConfig, *, window: int,
                          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode.  x (B, 1, d); cache (B, Hkv, S_max, dh);
    pos — int32 current length: a scalar shared by every row, or (B,),
    one per row.  Returns (out, new_k, new_v)."""
    B = x.shape[0]
    dh = cfg.resolved_head_dim
    compute = jnp.bfloat16 if rcfg.dtype == "bfloat16" else jnp.float32

    q = _split_heads(dense(p["wq"], x, compute), cfg.n_heads, dh)
    k = _split_heads(dense(p["wk"], x, compute), cfg.n_kv_heads, dh)
    v = _split_heads(dense(p["wv"], x, compute), cfg.n_kv_heads, dh)
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos.reshape(-1, 1, 1)                  # (B or 1, 1, S=1)
    q = apply_rope(q, positions, cfg.rope_fraction, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_fraction, cfg.rope_theta)

    if pos.ndim:
        # each row writes at its own position: a scatter of B slices,
        # not a mask over the whole cache
        write = jax.vmap(partial(jax.lax.dynamic_update_slice_in_dim, axis=1))
    else:
        write = partial(jax.lax.dynamic_update_slice_in_dim, axis=2)
    cache_k = write(cache_k, k.astype(cache_k.dtype), pos)
    cache_v = write(cache_v, v.astype(cache_v.dtype), pos)

    scale = cfg.query_scale if cfg.query_scale is not None \
        else 1.0 / math.sqrt(dh)
    q1 = q[:, :, 0]                                    # (B, Hq, dh)
    kv_len = pos + 1
    if rcfg.kernels == "pallas":
        o = decode_attention(q1, cache_k, cache_v, kv_len=kv_len,
                             window=window, softcap=cfg.attn_softcap,
                             scale=scale)
    else:
        o = decode_attention_ref(q1, cache_k, cache_v, kv_len=kv_len,
                                 window=window, softcap=cfg.attn_softcap,
                                 scale=scale)
    return dense(p["wo"], o[:, None].reshape(B, 1, -1), compute), \
        cache_k, cache_v


# --------------------------------------------------------------------------
# Ring-append decode — the mp_split fix for sequence-sharded caches
# --------------------------------------------------------------------------
# Writing one token into a sequence-SHARDED cache makes SPMD emit guarded
# selects + full-buffer converts (measured: 0.56 TB/step on qwen2.5-32b).
# Instead, appends go to a small REPLICATED ring (B, Hkv, R, dh) — a local
# DUS — and a separate `flush` merges the ring into the sharded main cache
# every R tokens (amortized R×).  Attention combines the two partial
# softmaxes (flash combine).

def _partial_softmax_attend(q, k, v, valid_len, scale, softcap, offset=0):
    """Returns (num (B,Hq,D), max (B,Hq,1), denom (B,Hq,1)) over k/v
    positions [0, valid_len); `offset` shifts the absolute position."""
    B, Hq, D = q.shape
    _, Hkv, S, _ = k.shape
    G = Hq // Hkv
    qf = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bhkd->bhgk", qf, k,
                   preferred_element_type=jnp.float32) * scale
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    mask = jnp.arange(S) < valid_len
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.maximum(m, NEG_INF + 1)         # guard all-masked rows
    p = jnp.exp(s - m)
    p = jnp.where(mask[None, None, None], p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    num = jnp.einsum("bhgk,bhkd->bhgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return (num.reshape(B, Hq, D), m.reshape(B, Hq, 1),
            l.reshape(B, Hq, 1))


def attention_decode_step_ring(p: Params, x: jax.Array,
                               cache_k: jax.Array, cache_v: jax.Array,
                               ring_k: jax.Array, ring_v: jax.Array,
                               pos: jax.Array, base: jax.Array,
                               cfg: ArchConfig, rcfg: RunConfig
                               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Ring decode (full attention only).  Main cache holds [0, base);
    ring holds [base, pos]; slot = pos - base < R.  Returns
    (out, new_ring_k, new_ring_v); the main cache is NOT touched.
    ``pos`` is a scalar: every row shares one base."""
    if jnp.ndim(pos):
        raise ValueError(
            "ring decode takes one scalar position for the whole batch "
            f"(rows share one base), got pos of shape {jnp.shape(pos)}")
    B = x.shape[0]
    dh = cfg.resolved_head_dim
    compute = jnp.bfloat16 if rcfg.dtype == "bfloat16" else jnp.float32

    q = _split_heads(dense(p["wq"], x, compute), cfg.n_heads, dh)
    k = _split_heads(dense(p["wk"], x, compute), cfg.n_kv_heads, dh)
    v = _split_heads(dense(p["wv"], x, compute), cfg.n_kv_heads, dh)
    positions = jnp.full((1,), pos, jnp.int32)
    q = apply_rope(q, positions, cfg.rope_fraction, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_fraction, cfg.rope_theta)

    slot = pos - base
    ring_k = jax.lax.dynamic_update_slice_in_dim(
        ring_k, k.astype(ring_k.dtype), slot, axis=2)
    ring_v = jax.lax.dynamic_update_slice_in_dim(
        ring_v, v.astype(ring_v.dtype), slot, axis=2)

    scale = cfg.query_scale if cfg.query_scale is not None \
        else 1.0 / math.sqrt(dh)
    q1 = q[:, :, 0]
    n1, m1, l1 = _partial_softmax_attend(
        q1, cache_k, cache_v, base, scale, cfg.attn_softcap)
    n2, m2, l2 = _partial_softmax_attend(
        q1, ring_k, ring_v, slot + 1, scale, cfg.attn_softcap)
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    denom = l1 * a1 + l2 * a2
    denom = jnp.where(denom == 0.0, 1.0, denom)
    o = ((n1 * a1 + n2 * a2) / denom).astype(q1.dtype)
    return dense(p["wo"], o[:, None].reshape(B, 1, -1), compute), \
        ring_k, ring_v


def flush_ring(cache_k, cache_v, ring_k, ring_v, base):
    """Merge the full ring into the main cache at `base` (every R steps).
    Works on both unstacked (B, Hkv, S, dh) and layer-stacked
    (rep, B, Hkv, S, dh) leaves — the seq axis is ndim-2."""
    axis = cache_k.ndim - 2
    ck = jax.lax.dynamic_update_slice_in_dim(
        cache_k, ring_k.astype(cache_k.dtype), base, axis=axis)
    cv = jax.lax.dynamic_update_slice_in_dim(
        cache_v, ring_v.astype(cache_v.dtype), base, axis=axis)
    return ck, cv
