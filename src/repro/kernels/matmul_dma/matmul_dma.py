"""Pallas TPU kernel: descriptor-driven double-buffered blocked matmul.

The Manticore case study (paper §3.5) is the blueprint: a cluster DMA
streams tiles from long-latency memory into local SRAM while the compute
units work on the previous tile — double buffering.  On TPU, the Pallas
pipeline plays the cluster-DMA role: the grid walks (m, n, k) tiles, the
hardware DMA prefetches block (k+1) while the MXU contracts block k, and
the iDMA legalizer (`plan_nd_copy`) picks MXU-aligned tile shapes
(multiples of 128 on the contraction/lane dims).

Accumulation is kept in an fp32 VMEM scratch across the sequential k steps
(dataflow element of the transport layer); the optional in-stream epilogue
(cast / scale / bias-free activation) is applied when the last k block
retires, i.e. *while the data is in flight* back to HBM.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM = 256
DEFAULT_BK = 512
DEFAULT_BN = 256


def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *,
                   n_k: int, k_tail: int, epilogue: Optional[Callable]):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    w = w_ref[...]
    if k_tail:
        # the last k block runs past the contraction; its stale VMEM
        # columns of x and rows of w would be summed in: zero them
        valid = jnp.where(k == n_k - 1, k_tail, x.shape[1])
        x = jnp.where(jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
                      < valid, x, jnp.zeros_like(x))
        w = jnp.where(jax.lax.broadcasted_iota(jnp.int32, w.shape, 0)
                      < valid, w, jnp.zeros_like(w))
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _retire():
        out = acc_ref[...]
        if epilogue is not None:
            out = epilogue(out)
        o_ref[...] = out.astype(o_ref.dtype)


def matmul_pallas(x: jax.Array, w: jax.Array,
                  block: Optional[Tuple[int, int, int]] = None,
                  out_dtype=None,
                  epilogue: Optional[Callable] = None,
                  interpret: bool = False) -> jax.Array:
    """x @ w with (bm, bk, bn) VMEM tiles and fp32 accumulation.

    Shapes: x (M, K), w (K, N) → (M, N).  M/K/N need not divide the block:
    rows and columns past M and N are dropped when the output block is
    written, and the kernel zeroes the contraction tail past K.
    """
    M, K = x.shape
    K2, N = w.shape
    if K != K2:
        raise ValueError(f"contraction mismatch {x.shape} @ {w.shape}")
    bm, bk, bn = block or (min(DEFAULT_BM, M), min(DEFAULT_BK, K),
                           min(DEFAULT_BN, N))
    grid = (pl.cdiv(M, bm), pl.cdiv(N, bn), pl.cdiv(K, bk))
    out_dtype = out_dtype or x.dtype

    kernel = functools.partial(_matmul_kernel, n_k=grid[2], k_tail=K % bk,
                               epilogue=epilogue)
    flops = 2 * M * N * K
    bytes_accessed = (M * K * x.dtype.itemsize + K * N * w.dtype.itemsize +
                      M * N * jnp.dtype(out_dtype).itemsize)
    cost = pl.CostEstimate(flops=flops, bytes_accessed=bytes_accessed,
                           transcendentals=0)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=cost,
        interpret=interpret,
    )(x, w)
