"""Shared model primitives: initializers, norms, RoPE, embeddings.

Params are plain nested dicts of jax.Arrays; init functions are pure
(key → tree) and `jax.eval_shape`-compatible, which is how the dry-run
builds ShapeDtypeStruct trees without allocating 30-B-parameter models.
Sharding is *name-based*: `repro.dist.sharding` maps param tree paths to
PartitionSpecs, so no sharding metadata lives here.
"""

from __future__ import annotations

import math
import zlib
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


def truncated_normal(key, shape, stddev, dtype=jnp.float32):
    return stddev * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                                dtype)


def dense_init(key, d_in: int, d_out: int, bias: bool = False,
               stddev: Optional[float] = None,
               dtype=jnp.float32) -> Params:
    stddev = stddev if stddev is not None else 1.0 / math.sqrt(d_in)
    p = {"kernel": truncated_normal(key, (d_in, d_out), stddev, dtype)}
    if bias:
        p["bias"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p: Params, x: jax.Array, compute_dtype=jnp.bfloat16) -> jax.Array:
    w = p["kernel"].astype(compute_dtype)
    y = x.astype(compute_dtype) @ w
    if "bias" in p:
        y = y + p["bias"].astype(compute_dtype)
    return y


def rmsnorm_init(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.zeros((d,), dtype)}     # (1 + scale) convention


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + p["scale"].astype(jnp.float32))).astype(dtype)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if cap <= 0:
        return x
    return cap * jnp.tanh(x / cap)


def activate(x: jax.Array, act: str) -> jax.Array:
    if act == "silu":
        return jax.nn.silu(x)
    if act == "gelu":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(f"unknown activation {act!r}")


# --------------------------------------------------------------------------
# Rotary position embeddings (full or partial dim — chatglm3 uses half)
# --------------------------------------------------------------------------

def rope_frequencies(head_dim: int, fraction: float, theta: float
                     ) -> jax.Array:
    rot = int(head_dim * fraction) // 2 * 2
    return 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))


def apply_rope(x: jax.Array, positions: jax.Array, fraction: float = 1.0,
               theta: float = 10000.0) -> jax.Array:
    """x (..., S, D); positions (..., S) or (S,)."""
    D = x.shape[-1]
    rot = int(D * fraction) // 2 * 2
    if rot == 0:
        return x
    freqs = rope_frequencies(D, fraction, theta)           # (rot/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (...,S,rot/2)
    cos = jnp.cos(angles)
    sin = jnp.sin(angles)
    while cos.ndim < x.ndim:
        cos = cos[None]
        sin = sin[None]
    x_rot = x[..., :rot].astype(jnp.float32)
    x1, x2 = x_rot[..., 0::2], x_rot[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    y = jnp.stack([y1, y2], axis=-1).reshape(x_rot.shape)
    return jnp.concatenate([y.astype(x.dtype), x[..., rot:]], axis=-1)


# --------------------------------------------------------------------------
# Embeddings
# --------------------------------------------------------------------------

def embedding_init(key, vocab: int, d: int, dtype=jnp.float32) -> Params:
    return {"table": truncated_normal(key, (vocab, d), 0.02, dtype)}


def embed(p: Params, tokens: jax.Array,
          compute_dtype=jnp.bfloat16) -> jax.Array:
    return p["table"].astype(compute_dtype)[tokens]


def unembed(p: Params, x: jax.Array, compute_dtype=jnp.bfloat16
            ) -> jax.Array:
    """Tied unembedding: logits = x @ tableᵀ (fp32 accumulate)."""
    return jnp.einsum("...d,vd->...v", x.astype(compute_dtype),
                      p["table"].astype(compute_dtype),
                      preferred_element_type=jnp.float32)


def fold_keys(key, *names: str):
    # crc32, not hash(): str hashes change from one process to the next,
    # and the same seed must give the same weights in every run
    return tuple(jax.random.fold_in(key, zlib.crc32(n.encode()) % (2 ** 31))
                 for n in names)
