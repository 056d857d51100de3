"""Per-architecture smoke tests: reduced same-family config, one forward /
train step on CPU, asserting output shapes + finite values (deliverable f).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY, get
from repro.configs.base import RunConfig, reduced
from repro.models import (init_decode_cache, init_lm, lm_decode_step,
                          lm_forward, lm_loss, lm_prefill)
from repro.models.encdec import encdec_loss, init_encdec
from repro.train.train_step import init_train_state, make_train_step

RCFG = RunConfig(kernels="xla", dtype="float32", remat=False,
                 scan_layers=True)
KEY = jax.random.PRNGKey(0)

ALL_ARCHS = sorted(REGISTRY)


def make_batch(cfg, B=2, S=32):
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    if cfg.vision is not None:
        batch["patch_embeds"] = jax.random.normal(
            KEY, (B, cfg.vision.n_patches, cfg.vision.patch_embed_dim))
    if cfg.encoder is not None:
        batch["frames"] = jax.random.normal(KEY, (B, 16, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = reduced(get(arch))
    cfg.validate()
    batch = make_batch(cfg)
    if cfg.family == "audio":
        params = init_encdec(KEY, cfg)
        loss, metrics = encdec_loss(params, batch, cfg, RCFG)
    else:
        params = init_lm(KEY, cfg)
        logits, aux = lm_forward(params, batch["tokens"], cfg, RCFG,
                                 patch_embeds=batch.get("patch_embeds"))
        assert logits.shape == (2, 32, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(logits)))
        loss, metrics = lm_loss(params, batch, cfg, RCFG)
    assert np.isfinite(float(loss))
    # random-init loss should be near ln(vocab)
    assert abs(float(metrics["loss"]) - np.log(cfg.vocab_size)) < 1.0


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_one_train_step_no_nans(arch):
    cfg = reduced(get(arch))
    state = init_train_state(KEY, cfg)
    step = make_train_step(cfg, RCFG)
    batch = make_batch(cfg)
    new_state, metrics = step(state, batch)
    assert int(new_state["step"]) == 1
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    assert float(metrics["grad_norm"]) > 0
    # params actually changed
    delta = jax.tree_util.tree_reduce(
        lambda acc, pair: acc + float(jnp.sum(jnp.abs(pair))),
        jax.tree_util.tree_map(lambda a, b: a - b, new_state["params"],
                               state["params"]), 0.0)
    assert delta > 0


DECODE_ARCHS = [a for a in ALL_ARCHS
                if REGISTRY[a].family != "audio"]


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_prefill_decode_equals_full_forward(arch):
    cfg = reduced(get(arch))
    params = init_lm(KEY, cfg)
    T, EXTRA = 12, 4
    toks = jax.random.randint(KEY, (1, T + EXTRA), 0, cfg.vocab_size)
    full_logits, _ = lm_forward(params, toks, cfg, RCFG)
    lg, cache = lm_prefill(params, toks[:, :T], cfg, RCFG, max_len=T + EXTRA)
    errs = [float(jnp.max(jnp.abs(lg - full_logits[:, T - 1])))]
    for t in range(T, T + EXTRA):
        lg, cache = lm_decode_step(params, cache, toks[:, t:t + 1],
                                   jnp.int32(t), cfg, RCFG)
        errs.append(float(jnp.max(jnp.abs(lg - full_logits[:, t]))))
    assert max(errs) < 1e-4, f"{arch}: decode diverges {errs}"


def test_gemma2_softcap_and_pattern():
    cfg = reduced(get("gemma2-2b"))
    kinds = [k for ks, rep in cfg.pattern for _ in range(rep) for k in ks]
    assert len(kinds) == cfg.n_layers
    assert "attn_swa" in kinds and "attn_full" in kinds


def test_moe_aux_loss_present():
    cfg = reduced(get("mixtral-8x7b"))
    params = init_lm(KEY, cfg)
    batch = make_batch(cfg)
    _, metrics = lm_loss(params, batch, cfg, RCFG)
    assert float(metrics["aux_loss"]) > 0


def test_full_configs_validate():
    for arch in ALL_ARCHS:
        cfg = get(arch)
        cfg.validate()
        assert cfg.total_layers == cfg.n_layers


def test_ring_decode_matches_forward():
    """Ring-append decode (+ flush every R) == full forward (§Perf cell 3)."""
    from repro.models.lm import flush_decode_caches
    from repro.models import init_decode_cache, lm_decode_step
    cfg = reduced(get("qwen2.5-32b"))
    params = init_lm(KEY, cfg)
    T, R = 13, 4
    toks = jax.random.randint(KEY, (1, T), 0, cfg.vocab_size)
    full, _ = lm_forward(params, toks, cfg, RCFG)
    cache = init_decode_cache(1, 32, cfg, jnp.float32, ring=R)
    errs = []
    for t in range(T):
        lg, cache = lm_decode_step(params, cache, toks[:, t:t + 1],
                                   jnp.int32(t), cfg, RCFG)
        errs.append(float(jnp.max(jnp.abs(lg - full[:, t]))))
        if (t + 1) % R == 0:
            cache = flush_decode_caches(cache, jnp.int32(t + 1 - R))
    assert max(errs) < 1e-4, errs


def test_ring_decode_refuses_per_row_positions():
    cfg = reduced(get("qwen2.5-32b"))
    params = init_lm(KEY, cfg)
    cache = init_decode_cache(2, 32, cfg, jnp.float32, ring=4)
    with pytest.raises(ValueError, match="one scalar position"):
        lm_decode_step(params, cache, jnp.zeros((2, 1), jnp.int32),
                       jnp.asarray([3, 5], jnp.int32), cfg, RCFG)


# window 8 so that rows past position 8 decode through a live window
@pytest.mark.parametrize("arch,window", [
    ("chatglm3-6b", None), ("gemma2-2b", 8), ("mamba2-1.3b", None),
    ("hymba-1.5b", 8)])
def test_mixed_positions_match_rows_alone(arch, window):
    """Rows at different positions through one `lm_decode_step` agree with
    each row decoded alone; a scalar position is the all-equal vector."""
    cfg = reduced(get(arch))
    if window is not None:
        cfg = dataclasses.replace(cfg, window=window)
    params = init_lm(KEY, cfg)
    step = jax.jit(lm_decode_step, static_argnums=(4, 5))
    S = 24
    lengths = [1, 5, 12, 19]
    rows = []
    for i, n in enumerate(lengths):
        toks = jax.random.randint(jax.random.fold_in(KEY, i), (1, n + 1), 0,
                                  cfg.vocab_size)
        _, cache = lm_prefill(params, toks[:, :n], cfg, RCFG, max_len=S)
        rows.append((cache, toks[:, n:], n))

    def stack(caches):
        return jax.tree_util.tree_map(
            lambda *a: jnp.concatenate(a, axis=1), *caches)

    caches = stack([c for c, _, _ in rows])
    cur = jnp.concatenate([t for _, t, _ in rows])
    pos = jnp.asarray(lengths, jnp.int32)
    logits, out = step(params, caches, cur, pos, cfg, RCFG)
    for j, (cache, tok, n) in enumerate(rows):
        lg1, out1 = step(params, cache, tok, jnp.int32(n), cfg, RCFG)
        np.testing.assert_allclose(logits[j], lg1[0], rtol=0, atol=1e-5)
        for seg, seg1, seg_in in zip(out, out1, cache):
            for c, c1, c_in in zip(seg, seg1, seg_in):
                for name in c:
                    row = jax.tree_util.tree_map(
                        lambda a: np.asarray(a[:, j:j + 1]), c[name])
                    if name not in ("k", "v"):       # recurrent state
                        jax.tree_util.tree_map(
                            lambda a, b: np.testing.assert_allclose(
                                a, b, rtol=0, atol=1e-5), row, c1[name])
                        continue
                    # written at the row's own position, and nowhere
                    # else: the rest is the row's cache as it came in
                    np.testing.assert_allclose(
                        row[:, :, :, n], np.asarray(c1[name])[:, :, :, n],
                        rtol=0, atol=1e-5)
                    assert np.array_equal(
                        np.delete(row, n, axis=3),
                        np.delete(np.asarray(c_in[name]), n, axis=3))

    # a scalar position: the program as it was, and the same numbers as
    # that position given once per row
    same = jnp.full((len(lengths),), 12, jnp.int32)
    lg_s, out_s = step(params, caches, cur, jnp.int32(12), cfg, RCFG)
    lg_v, out_v = step(params, caches, cur, same, cfg, RCFG)
    assert np.array_equal(lg_s, lg_v)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool(np.array_equal(a, b)), out_s, out_v))
