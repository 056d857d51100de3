"""`StepLM` — the model's jitted prefill/decode steps bound to the
continuous-batching front door.

The dynamic batch is served by *stacking*: every decoding request, at
whatever position, is stacked along the cache batch axis and run
through ONE `lm_decode_step` call with one position per row, then split
back — one weight pass per step, not one per row.  Stacking is not
bitwise row-independent: a row's float32 logits decoded among others
differ from the same row decoded alone in the last bits (about 1e-7 on
reduced models, CPU), so a token may flip where two logits tie to that
precision.  The benchmark's comparison with its float32 reference
(`logit_gap_mean`) is what holds served tokens to the model.

Per-request sampling state: greedy rows are exact ``argmax``; a
temperature row draws with a key folded from ``(engine seed, rid,
step)`` — a counter-based key, so the draw at step ``t`` of request
``r`` never depends on which other requests are in flight.

KV bytes on the DMA plane: the jax caches are the *logits* source of
truth, while the pool/staging/swap bytes the scheduler moves are a
deterministic hash mirror of the same (request, position, token)
history (`HashLM.kv_rows`).  The mirror keeps the descriptor plane
honest — a corrupted swap or a mis-gathered page would change gathered
bytes that tests digest-check — without forcing the float cache layout
through the byte pool.

Spans (`repro.obs`): ``model.prefill``, ``model.stack`` /
``model.decode`` / ``model.unstack`` around the step's decode call,
``model.sample`` per row with a ``model.sync`` inside it for each
blocking device→host read.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ArchConfig, RunConfig
from repro.models import lm_decode_step, lm_prefill
from .model import HashLM

# compiled once per (config, run config, cache capacity, shapes) and
# shared by every StepLM: a second server over one model compiles nothing
_prefill = jax.jit(lm_prefill, static_argnames=("cfg", "rcfg", "max_len"))
_decode = jax.jit(lm_decode_step, static_argnames=("cfg", "rcfg"))


class StepLM:
    """Model adapter over `lm_prefill` / `lm_decode_step`."""

    def __init__(self, cfg: ArchConfig, rcfg: RunConfig, params,
                 max_len: int, row_bytes: int, eos_token: int = -1,
                 seed: int = 0) -> None:
        self.cfg = cfg
        self.rcfg = rcfg
        self.vocab = cfg.vocab_size
        self.eos_token = eos_token
        self.params = params
        self.max_len = max_len
        self._mirror = HashLM(row_bytes, vocab=self.vocab,
                              eos_token=eos_token, seed=seed)
        self._key = jax.random.PRNGKey(seed)
        self._caches: Dict[int, object] = {}      # rid → B=1 cache pytree
        self._logits: Dict[int, jax.Array] = {}   # rid → pending (1, V)

    # -- DMA-plane byte contract (the hash mirror) ---------------------------

    def kv_rows(self, seed: int, tokens, start: int, end: int,
                which: str) -> np.ndarray:
        return self._mirror.kv_rows(seed, tokens, start, end, which)

    # -- lifecycle -----------------------------------------------------------

    def on_admit(self, req) -> None:
        """Run the real prefill for this request (B=1); its last-position
        logits become the first decode sample."""
        tokens = jnp.asarray(np.asarray(req.prompt, np.int32))[None, :]
        with obs.DEFAULT.span("model.prefill", req.rid):
            logits, caches = _prefill(self.params, tokens, cfg=self.cfg,
                                      rcfg=self.rcfg, max_len=self.max_len)
        self._caches[req.rid] = caches
        self._logits[req.rid] = logits

    def release(self, req) -> None:
        self._caches.pop(req.rid, None)
        self._logits.pop(req.rid, None)

    # -- decode --------------------------------------------------------------

    @staticmethod
    def _read(value: jax.Array, to: Callable):
        """``to(value)``: one blocking device→host read."""
        with obs.DEFAULT.span("model.sync"):
            return to(value)

    def _sample_row(self, req, logits_row: jax.Array) -> int:
        with obs.DEFAULT.span("model.sample", req.rid):
            if not self._read(jnp.isfinite(logits_row).all(), bool):
                # argmax and categorical would still return a token
                raise FloatingPointError(
                    f"request {req.rid}: non-finite logits at position "
                    f"{len(req.tokens) - 1}")
            if req.temperature <= 0:
                return self._read(jnp.argmax(logits_row), int)
            key = jax.random.fold_in(
                jax.random.fold_in(self._key, req.rid), len(req.tokens))
            return self._read(jax.random.categorical(
                key, logits_row / max(req.temperature, 1e-4)), int)

    def next_tokens(self, reqs, gathered: List[Tuple[np.ndarray,
                                                     np.ndarray]]
                    ) -> List[int]:
        """One token per request; ``gathered`` (the DMA-plane bytes) is
        validated by the tests' digests, not consumed for logits."""
        out: List[int] = [0] * len(reqs)
        idxs: List[int] = []
        for i, req in enumerate(reqs):
            if req.rid in self._logits:
                # first decode step: the prefill already produced these
                # logits (position len(prompt) - 1)
                out[i] = self._sample_row(req, self._logits.pop(req.rid)[0])
            else:
                idxs.append(i)
        if not idxs:
            return out
        group = [reqs[i] for i in idxs]
        rec = obs.DEFAULT
        with rec.span("model.stack"):
            caches = jax.tree_util.tree_map(
                lambda *leaves: jnp.concatenate(leaves, axis=1),
                *[self._caches[r.rid] for r in group])
            cur = jnp.asarray([[r.tokens[-1]] for r in group], jnp.int32)
            pos = jnp.asarray([len(r.tokens) - 1 for r in group], jnp.int32)
        with rec.span("model.decode"):
            logits, caches = _decode(self.params, caches, cur, pos,
                                     cfg=self.cfg, rcfg=self.rcfg)
        for j, (i, req) in enumerate(zip(idxs, group)):
            with rec.span("model.unstack", req.rid):
                self._caches[req.rid] = jax.tree_util.tree_map(
                    lambda a, j=j: a[:, j:j + 1], caches)
            out[i] = self._sample_row(req, logits[j])
        return out
