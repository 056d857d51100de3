"""Decoder-only LM assembly (dense / MoE / SSM / hybrid / VLM).

  init_lm           — full param tree (eval_shape-compatible)
  init_lm_params    — init_lm as one program, stored in rcfg.param_dtype
  lm_forward        — tokens (+ optional patch embeddings) → logits, aux
  lm_loss           — next-token cross entropy (sharded-vocab-safe)
  init_decode_cache — per-segment KV/SSM caches
  lm_decode_step    — one-token decode through the cache
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, RunConfig
from .common import (Params, dense, dense_init, embed, embedding_init,
                     fold_keys, rmsnorm, rmsnorm_init, softcap, unembed)
from .blocks import (block_decode_step, init_block_cache, init_segments,
                     segments_forward)
from .attention import flush_ring


def flush_decode_caches(caches, base):
    """Merge every layer's ring into its main cache at `base` (call every
    R decoded tokens; see attention_decode_step_ring)."""
    out = []
    for seg in caches:
        new_seg = []
        for c in seg:
            if "rk" in c:
                nk, nv = flush_ring(c["k"], c["v"], c["rk"], c["rv"], base)
                c = dict(c, k=nk, v=nv)
            new_seg.append(c)
        out.append(new_seg)
    return out


def init_lm(key, cfg: ArchConfig) -> Params:
    kw, kl, kh, kv = fold_keys(key, "embed", "layers", "head", "vision")
    p: Params = {
        "embed": embedding_init(kw, cfg.padded_vocab, cfg.d_model),
        "segments": init_segments(kl, cfg),
        "final_norm": rmsnorm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(kh, cfg.d_model, cfg.padded_vocab,
                                  stddev=0.02)
    if cfg.vision is not None:
        p["vision_proj"] = dense_init(kv, cfg.vision.patch_embed_dim,
                                      cfg.d_model)
    return p


@functools.partial(jax.jit, static_argnums=(1, 2))
def init_lm_params(key, cfg: ArchConfig, rcfg: RunConfig) -> Params:
    """`init_lm` compiled as one program whose floating leaves come out in
    ``rcfg.param_dtype``.  A bfloat16 init never holds the float32 tree
    on the device next to its copy (gemma2-2b: 10.5 GB in float32)."""
    dtype = jnp.dtype(rcfg.param_dtype)
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a,
        init_lm(key, cfg))


def _logits(p: Params, x: jax.Array, cfg: ArchConfig,
            compute_dtype) -> jax.Array:
    if cfg.tie_embeddings:
        logits = unembed(p["embed"], x, compute_dtype)
    else:
        logits = dense(p["lm_head"], x, compute_dtype) \
            .astype(jnp.float32)
    logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    if cfg.padded_vocab != cfg.vocab_size:
        # mask the pad rows out of the softmax
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(pad_mask, -1e30, logits)
    return logits


def _embed_in(p: Params, tokens: jax.Array, cfg: ArchConfig,
              compute_dtype,
              patch_embeds: Optional[jax.Array] = None) -> jax.Array:
    x = embed(p["embed"], tokens, compute_dtype)
    if cfg.tie_embeddings:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), compute_dtype)
    if cfg.vision is not None and patch_embeds is not None:
        proj = dense(p["vision_proj"], patch_embeds.astype(compute_dtype),
                     compute_dtype)
        n = proj.shape[1]
        x = jnp.concatenate([proj, x[:, n:]], axis=1)
    return x


def lm_forward(p: Params, tokens: jax.Array, cfg: ArchConfig,
               rcfg: RunConfig,
               patch_embeds: Optional[jax.Array] = None,
               constrain=None) -> Tuple[jax.Array, jax.Array]:
    """tokens (B, S) → (logits (B, S, V) fp32, aux loss)."""
    compute = jnp.bfloat16 if rcfg.dtype == "bfloat16" else jnp.float32
    x = _embed_in(p, tokens, cfg, compute, patch_embeds)
    positions = jnp.arange(tokens.shape[1])
    x, aux = segments_forward(p["segments"], x, cfg, rcfg,
                              positions=positions, constrain=constrain)
    x = rmsnorm(p["final_norm"], x)
    return _logits(p, x, cfg, compute), aux


def lm_loss(p: Params, batch: Dict[str, jax.Array], cfg: ArchConfig,
            rcfg: RunConfig, constrain=None) -> Tuple[jax.Array, Dict]:
    """Next-token CE; `batch` = {"tokens": (B,S)[, "patch_embeds"]}.

    Large sharded vocab: the logsumexp/gather run in fp32 over bf16 logits;
    XLA inserts the vocab-axis collectives.
    """
    tokens = batch["tokens"]
    logits, aux = lm_forward(p, tokens, cfg, rcfg,
                             patch_embeds=batch.get("patch_embeds"),
                             constrain=constrain)
    targets = tokens[:, 1:]
    lg = logits[:, :-1]
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    nll = lse - picked
    mask = jnp.ones_like(nll)
    if cfg.vision is not None:
        # do not train on patch positions
        n = cfg.vision.n_patches
        mask = mask.at[:, :max(n - 1, 0)].set(0.0)
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    metrics = {"loss": loss, "aux_loss": aux,
               "tokens": jnp.sum(mask)}
    return loss + aux, metrics


def lm_prefill(p: Params, tokens: jax.Array, cfg: ArchConfig,
               rcfg: RunConfig, max_len: Optional[int] = None,
               patch_embeds: Optional[jax.Array] = None,
               constrain=None):
    """Prefill: full forward that also materializes the decode caches.

    Returns (last_logits (B, V), caches) where attention caches are padded
    out to `max_len` (the decode session capacity).
    """
    compute = jnp.bfloat16 if rcfg.dtype == "bfloat16" else jnp.float32
    S = tokens.shape[1]
    max_len = max_len or S
    x = _embed_in(p, tokens, cfg, compute, patch_embeds)
    positions = jnp.arange(S)
    x, _aux, caches = segments_forward(
        p["segments"], x, cfg, rcfg, positions=positions,
        constrain=constrain, collect_caches=True)
    x = rmsnorm(p["final_norm"], x)
    logits = _logits(p, x[:, -1:], cfg, compute)[:, 0]

    def pad_cache(c):
        def pad_leaf_kv(a):
            # (rep, B, Hkv, S, dh) → pad S to max_len
            pad = max_len - a.shape[3]
            if pad <= 0:
                return a
            return jnp.pad(a, ((0, 0),) * 3 + ((0, pad), (0, 0)))
        out = dict(c)
        if "k" in c:
            out["k"] = pad_leaf_kv(c["k"])
            out["v"] = pad_leaf_kv(c["v"])
        return out

    caches = [[pad_cache(c) for c in seg] for seg in caches]
    return logits, caches


# --------------------------------------------------------------------------
# Decode
# --------------------------------------------------------------------------

def init_decode_cache(batch: int, max_len: int, cfg: ArchConfig,
                      dtype=jnp.bfloat16, ring: int = 0
                      ) -> List[List[Dict[str, Any]]]:
    """Per-segment, per-kind stacked caches (leading dim = repeat)."""
    caches: List[List[Dict[str, Any]]] = []
    for kinds, rep in cfg.pattern:
        seg = []
        for kind in kinds:
            one = init_block_cache(batch, max_len, cfg, kind, dtype,
                                   ring=ring)
            seg.append(jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (rep,) + a.shape)
                .copy() if rep > 1 else a[None], one))
        caches.append(seg)
    return caches


def lm_decode_step(p: Params, caches: List[List[Dict[str, Any]]],
                   tokens: jax.Array, pos: jax.Array, cfg: ArchConfig,
                   rcfg: RunConfig
                   ) -> Tuple[jax.Array, List[List[Dict[str, Any]]]]:
    """tokens (B, 1) current token; pos — current cache fill, an int32
    scalar shared by every row or a (B,) vector, one per row (ring
    caches take a scalar only).  Returns (logits (B, V) fp32, updated
    caches)."""
    compute = jnp.bfloat16 if rcfg.dtype == "bfloat16" else jnp.float32
    x = _embed_in(p, tokens, cfg, compute)

    new_caches: List[List[Dict[str, Any]]] = []
    for (kinds, rep), stacks, cstacks in zip(cfg.pattern, p["segments"],
                                             caches):
        new_seg: List[Dict[str, Any]] = []
        if rcfg.scan_layers and rep > 1:
            # scan over the repeat dim, threading x and collecting caches
            def body(h, inp):
                outs = []
                for kind, lp, lc in zip(kinds, inp[0], inp[1]):
                    h, nc = block_decode_step(lp, h, lc, pos, cfg, rcfg,
                                              kind)
                    outs.append(nc)
                return h, tuple(outs)

            x, outs = jax.lax.scan(body, x, (tuple(stacks), tuple(cstacks)))
            new_seg = list(outs)
        else:
            outs_acc = [[] for _ in kinds]
            for r in range(rep):
                for ki, (kind, st, cs) in enumerate(
                        zip(kinds, stacks, cstacks)):
                    lp = jax.tree_util.tree_map(lambda a: a[r], st)
                    lc = jax.tree_util.tree_map(lambda a: a[r], cs)
                    x, nc = block_decode_step(lp, x, lc, pos, cfg, rcfg,
                                              kind)
                    outs_acc[ki].append(nc)
            for ki in range(len(kinds)):
                new_seg.append(jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *outs_acc[ki]))
        new_caches.append(new_seg)

    x = rmsnorm(p["final_norm"], x)
    logits = _logits(p, x, cfg, compute)[:, 0]
    return logits, new_caches
