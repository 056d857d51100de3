"""Transformer/SSM/hybrid blocks + the segment-scan machinery.

A *block* is one residual layer of a given kind:
  attn_full / attn_swa — [norm → attention → (+)] [norm → FFN|MoE → (+)]
  ssm                  — [norm → mamba2 → (+)]      (no FFN in Mamba-2)
  hybrid / hybrid_full — [norm → ½(attn ⊕ ssm) → (+)] [norm → FFN → (+)]

Layer stacks are expressed as segments ((kinds...), repeat) and executed
with `lax.scan` over stacked params — HLO stays O(#segments).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ArchConfig, RunConfig, ATTN_FULL, ATTN_SWA,
                                SSM)
from .common import Params, fold_keys, rmsnorm, rmsnorm_init
from .attention import (attention_decode_step, attention_decode_step_ring,
                        attention_forward, init_attention)
from .ffn import ffn_forward, init_ffn
from .moe import init_moe, moe_forward
from .ssm import (init_ssm, init_ssm_cache, ssm_decode_step, ssm_forward)

HYBRID_KINDS = ("hybrid", "hybrid_full")
ATTN_KINDS = (ATTN_FULL, ATTN_SWA) + HYBRID_KINDS


def _window_for(kind: str, cfg: ArchConfig) -> int:
    if kind in (ATTN_SWA, "hybrid"):
        return cfg.window
    return 0


def _has_ffn(kind: str, cfg: ArchConfig) -> bool:
    return cfg.d_ff > 0 or cfg.moe is not None


def init_block(key, cfg: ArchConfig, kind: str) -> Params:
    ka, ks, kf, _ = fold_keys(key, "attn", "ssm", "ffn", "norms")
    p: Params = {"ln1": rmsnorm_init(cfg.d_model)}
    if kind in ATTN_KINDS:
        p["attn"] = init_attention(ka, cfg)
    if kind == SSM or kind in HYBRID_KINDS:
        p["ssm"] = init_ssm(ks, cfg)
    if kind in HYBRID_KINDS:
        p["attn_out_norm"] = rmsnorm_init(cfg.d_model)
        p["ssm_out_norm"] = rmsnorm_init(cfg.d_model)
    if _has_ffn(kind, cfg) and kind != SSM:
        p["ln2"] = rmsnorm_init(cfg.d_model)
        if cfg.moe is not None:
            p["moe"] = init_moe(kf, cfg)
        else:
            p["ffn"] = init_ffn(kf, cfg.d_model, cfg.d_ff)
    if cfg.post_block_norm:
        p["post_ln1"] = rmsnorm_init(cfg.d_model)
        if "ln2" in p:
            p["post_ln2"] = rmsnorm_init(cfg.d_model)
    return p


def _mixer_forward(p: Params, h: jax.Array, cfg: ArchConfig,
                   rcfg: RunConfig, kind: str,
                   positions: Optional[jax.Array],
                   collect_cache: bool = False):
    window = _window_for(kind, cfg)
    cache: Dict[str, Any] = {}
    if kind in HYBRID_KINDS:
        a = attention_forward(p["attn"], h, cfg, rcfg, window=window,
                              positions=positions, return_kv=collect_cache)
        if collect_cache:
            a, (cache["k"], cache["v"]) = a
        s = ssm_forward(p["ssm"], h, cfg, rcfg, return_state=collect_cache)
        if collect_cache:
            s, cache["ssm"] = s
        out = 0.5 * (rmsnorm(p["attn_out_norm"], a) +
                     rmsnorm(p["ssm_out_norm"], s))
    elif kind == SSM:
        out = ssm_forward(p["ssm"], h, cfg, rcfg,
                          return_state=collect_cache)
        if collect_cache:
            out, cache["ssm"] = out
    else:
        out = attention_forward(p["attn"], h, cfg, rcfg, window=window,
                                positions=positions,
                                return_kv=collect_cache)
        if collect_cache:
            out, (cache["k"], cache["v"]) = out
    return (out, cache) if collect_cache else out


def block_forward(p: Params, x: jax.Array, cfg: ArchConfig, rcfg: RunConfig,
                  kind: str, positions: Optional[jax.Array] = None,
                  collect_cache: bool = False):
    """Returns (x, aux_loss[, cache])."""
    aux = jnp.zeros((), jnp.float32)
    h = _mixer_forward(p, rmsnorm(p["ln1"], x), cfg, rcfg, kind, positions,
                       collect_cache=collect_cache)
    cache = None
    if collect_cache:
        h, cache = h
    if cfg.post_block_norm:
        h = rmsnorm(p["post_ln1"], h)
    x = x + h
    if "ln2" in p:
        h = rmsnorm(p["ln2"], x)
        if cfg.moe is not None:
            h, aux = moe_forward(p["moe"], h, cfg, rcfg)
        else:
            h = ffn_forward(p["ffn"], h, cfg.act,
                            jnp.bfloat16 if rcfg.dtype == "bfloat16"
                            else jnp.float32)
        if cfg.post_block_norm:
            h = rmsnorm(p["post_ln2"], h)
        x = x + h
    if collect_cache:
        return x, aux, cache
    return x, aux


# --------------------------------------------------------------------------
# Decode caches
# --------------------------------------------------------------------------

def init_block_cache(batch: int, max_len: int, cfg: ArchConfig, kind: str,
                     dtype=jnp.bfloat16, ring: int = 0) -> Dict[str, Any]:
    cache: Dict[str, Any] = {}
    if kind in ATTN_KINDS:
        dh = cfg.resolved_head_dim
        # Linear cache; window masking uses absolute positions, which keeps
        # decode == prefill exactly.
        cache["k"] = jnp.zeros((batch, cfg.n_kv_heads, max_len, dh), dtype)
        cache["v"] = jnp.zeros((batch, cfg.n_kv_heads, max_len, dh), dtype)
        if ring > 0 and _window_for(kind, cfg) == 0:
            # replicated append ring (see attention_decode_step_ring)
            cache["rk"] = jnp.zeros((batch, cfg.n_kv_heads, ring, dh),
                                    dtype)
            cache["rv"] = jnp.zeros((batch, cfg.n_kv_heads, ring, dh),
                                    dtype)
    if kind == SSM or kind in HYBRID_KINDS:
        cache["ssm"] = init_ssm_cache(batch, cfg, jnp.float32)
    return cache


def block_decode_step(p: Params, x: jax.Array, cache: Dict[str, Any],
                      pos: jax.Array, cfg: ArchConfig, rcfg: RunConfig,
                      kind: str) -> Tuple[jax.Array, Dict[str, Any]]:
    """One decode token through one block.  ``pos``: a scalar or (B,),
    one per row (`attention_decode_step`); the SSM state needs none."""
    new_cache = dict(cache)
    h = rmsnorm(p["ln1"], x)
    window = _window_for(kind, cfg)

    def attn_branch(h):
        if "rk" in cache:
            R = cache["rk"].shape[2]
            base = (pos // R) * R
            out, rk, rv = attention_decode_step_ring(
                p["attn"], h, cache["k"], cache["v"], cache["rk"],
                cache["rv"], pos, base, cfg, rcfg)
            new_cache.update(rk=rk, rv=rv)
            return out, cache["k"], cache["v"]
        out, ck, cv = attention_decode_step(
            p["attn"], h, cache["k"], cache["v"], pos, cfg, rcfg,
            window=window)
        return out, ck, cv

    if kind in HYBRID_KINDS:
        a, ck, cv = attn_branch(h)
        s, new_ssm = ssm_decode_step(p["ssm"], h, cache["ssm"], cfg, rcfg)
        new_cache.update(k=ck, v=cv, ssm=new_ssm)
        h = 0.5 * (rmsnorm(p["attn_out_norm"], a) +
                   rmsnorm(p["ssm_out_norm"], s))
    elif kind == SSM:
        h, new_ssm = ssm_decode_step(p["ssm"], h, cache["ssm"], cfg, rcfg)
        new_cache["ssm"] = new_ssm
    else:
        h, ck, cv = attn_branch(h)
        new_cache.update(k=ck, v=cv)
    if cfg.post_block_norm:
        h = rmsnorm(p["post_ln1"], h)
    x = x + h

    if "ln2" in p:
        h = rmsnorm(p["ln2"], x)
        if cfg.moe is not None:
            h, _ = moe_forward(p["moe"], h, cfg, rcfg)
        else:
            h = ffn_forward(p["ffn"], h, cfg.act,
                            jnp.bfloat16 if rcfg.dtype == "bfloat16"
                            else jnp.float32)
        if cfg.post_block_norm:
            h = rmsnorm(p["post_ln2"], h)
        x = x + h
    return x, new_cache


# --------------------------------------------------------------------------
# Segment scan: init + forward over ((kinds...), repeat) stacks
# --------------------------------------------------------------------------

def init_segments(key, cfg: ArchConfig) -> List[List[Params]]:
    """Returns per-segment, per-kind stacked params (leading dim = repeat)."""
    segments = []
    layer = 0
    for si, (kinds, rep) in enumerate(cfg.pattern):
        seg = []
        for ki, kind in enumerate(kinds):
            keys = jax.random.split(
                jax.random.fold_in(key, si * 97 + ki), rep)
            seg.append(jax.vmap(
                lambda k: init_block(k, cfg, kind))(keys))
            layer += rep
        segments.append(seg)
    return segments


def segments_forward(seg_params: List[List[Params]], x: jax.Array,
                     cfg: ArchConfig, rcfg: RunConfig,
                     positions: Optional[jax.Array] = None,
                     constrain=None, collect_caches: bool = False):
    """Scan the full stack; returns (x, total_aux[, caches])."""
    total_aux = jnp.zeros((), jnp.float32)
    all_caches: List[List[Any]] = []

    for (kinds, rep), stacks in zip(cfg.pattern, seg_params):

        def body(carry, layer_params):
            h, aux = carry
            caches = []
            for kind, lp in zip(kinds, layer_params):
                out = block_forward(lp, h, cfg, rcfg, kind, positions,
                                    collect_cache=collect_caches)
                if collect_caches:
                    h, a, c = out
                    caches.append(c)
                else:
                    h, a = out
                aux = aux + a
            if constrain is not None:
                h = constrain(h)
            return (h, aux), tuple(caches)

        if rcfg.remat and not collect_caches:
            body = jax.checkpoint(body)
        if rcfg.scan_layers and rep > 1:
            (x, total_aux), seg_caches = jax.lax.scan(
                body, (x, total_aux), tuple(stacks))
        else:
            caches_acc = None
            for r in range(rep):
                sl = jax.tree_util.tree_map(lambda a: a[r], tuple(stacks))
                (x, total_aux), cs = body((x, total_aux), sl)
                if collect_caches:
                    if caches_acc is None:
                        caches_acc = [[c] for c in cs]
                    else:
                        for acc, c in zip(caches_acc, cs):
                            acc.append(c)
            seg_caches = tuple(
                jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *acc)
                for acc in (caches_acc or [])) if collect_caches else ()
        if collect_caches:
            all_caches.append(list(seg_caches))
    if collect_caches:
        return x, total_aux, all_caches
    return x, total_aux
