"""Pallas TPU kernel: Mamba-2 SSD (state-space duality) chunked scan.

[arXiv:2405.21060] §6: the selective state-space recurrence

    h_t = exp(A·dt_t) · h_{t-1} + dt_t · B_t ⊗ x_t
    y_t = C_tᵀ h_t + D ⊙ x_t

is evaluated chunk-wise: a quadratic *intra-chunk* term (an (L, L) masked
score matrix — MXU work) plus a rank-N *inter-chunk* state carried across
chunks (the sequential dimension).  This maps perfectly onto the iDMA
transport story: per (batch, head) the chunk stream is the burst sequence,
the (N, P) state in VMEM scratch is the dataflow element, and the x/B/C
tiles are prefetched by the pipeline while the MXU contracts the previous
chunk.

Layouts (P = headdim, N = state dim, G = B/C groups):
  x (B, H, S, P) · dt (B, H, S) · A (H,) · D (H,) · B/C (B, G, S, N)
Grid: (B, H, S/L) — chunks sequential innermost.

Mosaic tiles the last two dims of every block by (8, 128), and has no
cumsum.  So the wrapper takes the per-chunk cumulative decay
``cum = cumsum(A·dt)`` in XLA, and hands ``dt`` and ``cum`` to the
kernel twice each: as (B, H, 1, S) rows and (B, H, S, 1) columns, whose
(1, L) and (L, 1) blocks are legal.  ``A`` and ``D`` are per-head
scalars in SMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128


def _ssd_kernel(x_ref, dtr_ref, dtc_ref, cumr_ref, cumc_ref, a_ref, d_ref,
                b_ref, c_ref, y_ref, state_out_ref, state_ref, *, L: int,
                n_chunks: int):
    h = pl.program_id(1)
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)           # (L, P)
    dt_row = dtr_ref[0, 0]                        # (1, L)
    dt_col = dtc_ref[0, 0]                        # (L, 1)
    cum_row = cumr_ref[0, 0]                      # (1, L)  inclusive
    cum_col = cumc_ref[0, 0]                      # (L, 1)
    a = a_ref[h]                                  # scalar (negative)
    dsk = d_ref[h]                                # scalar skip
    bb = b_ref[0, 0].astype(jnp.float32)          # (L, N)
    cc = c_ref[0, 0].astype(jnp.float32)          # (L, N)
    total = jnp.sum(a * dt_row, axis=1, keepdims=True)    # (1, 1)

    # intra-chunk: scores[t, s] = (C_t·B_s) * exp(cum_t - cum_s) * dt_s, s<=t
    mask = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    decay = jnp.where(mask, jnp.exp(cum_col - cum_row), 0.0)   # (L, L)
    scores = jax.lax.dot_general(cc, bb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    scores = scores * decay * dt_row
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: y_t += exp(cum_t) * C_t @ h_prev
    h_prev = state_ref[...]                       # (N, P)
    y = y + jnp.exp(cum_col) * jax.lax.dot_general(
        cc, h_prev, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: h = exp(total)·h_prev + Σ_s exp(total-cum_s)·dt_s·B_s⊗x_s
    w = jnp.exp(total - cum_col) * dt_col         # (L, 1)
    state_ref[...] = jnp.exp(total) * h_prev + jax.lax.dot_general(
        (bb * w).T, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    y_ref[0, 0] = (y + dsk * x).astype(y_ref.dtype)

    @pl.when(c_idx == n_chunks - 1)
    def _final_state():
        state_out_ref[0, 0] = state_ref[...]


def ssd_pallas(x: jax.Array, dt: jax.Array, A: jax.Array, D: jax.Array,
               B: jax.Array, C: jax.Array,
               chunk: int = DEFAULT_CHUNK,
               return_state: bool = False,
               interpret: bool = False):
    """Returns y (B, H, S, P) [, final state (B, H, N, P)].  S must be a
    multiple of `chunk` (the framework pads sequences — legalizer rule)."""
    Bb, H, S, P = x.shape
    _, G, _, N = B.shape
    if S % chunk:
        raise ValueError(f"seq {S} not a multiple of chunk {chunk}")
    if H % G:
        raise ValueError(f"heads {H} not a multiple of groups {G}")
    hpg = H // G
    n_chunks = S // chunk
    grid = (Bb, H, n_chunks)

    dt = dt.astype(jnp.float32)
    A = A.astype(jnp.float32)
    cum = jnp.cumsum((A[None, :, None] * dt).reshape(Bb, H, n_chunks, chunk),
                     axis=-1).reshape(Bb, H, S)

    def rows(a):
        return a.reshape(Bb, H, 1, S)

    def cols(a):
        return a.reshape(Bb, H, S, 1)

    row_spec = pl.BlockSpec((1, 1, 1, chunk), lambda b, h, c: (b, h, 0, c))
    col_spec = pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kernel = functools.partial(_ssd_kernel, L=chunk, n_chunks=n_chunks)
    y, state = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            row_spec, col_spec, row_spec, col_spec, smem, smem,
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, h // hpg, c, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, h // hpg, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((Bb, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, rows(dt), cols(dt), rows(cum), cols(cum), A, D.astype(jnp.float32),
      B, C)
    if return_state:
        return y, state
    return y
