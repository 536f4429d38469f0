"""Pallas TPU kernel: fused Welford/Chan-merge streaming-moments update.

One grid step per machine (grid = (M,), fully parallel — machines never
share state). Each step loads its ``(block_c, block_d)`` chunk tile plus the
machine's running ``(mean, m2)`` into VMEM and fuses the whole update:

- batch moments of the chunk (masked mean + centered Gram via one MXU
  ``centᵀ·cent`` matmul);
- Chan's parallel-Welford merge of (n_a, mean_a, m2_a) with the chunk's
  (n_b, mean_b, m2_b), including the rank-one ``δδᵀ`` correction.

The per-machine scalars (valid-row count in the chunk, running count n_a)
ride in as a lane-broadcast ``(M, 1, 128)`` f32 operand — cols 0/1 — so the
kernel needs no SMEM scalar plumbing and runs identically in interpret mode.
Per-machine rows (scalars, mean) carry a unit middle axis so that the last
two dims of every block equal the array's, as the TPU's (8, 128) tiling
rule requires of blocks narrower than a tile.

Padding contract (``ops.py`` enforces): padded d-features MUST be zero in
the chunk *and* the state — a zero feature has zero chunk mean, zero
centered residual, and zero δ, so every padded row/col of mean/m2 stays
exactly zero through the merge. Padded C rows are excluded by the row mask
(they sit beyond the valid count), so they never touch the moments either.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _online_update_body(
    chunk_ref, sc_ref, mean_ref, m2_ref, mean_out_ref, m2_out_ref
):
    t = chunk_ref[0].astype(jnp.float32)  # (block_c, block_d)
    cc = sc_ref[0, :, 0:1]  # (1, 1) n_b: valid rows of this machine's chunk
    n_a = sc_ref[0, :, 1:2]  # (1, 1) running count
    mean0 = mean_ref[0].astype(jnp.float32)  # (1, block_d)
    m2_0 = m2_ref[0].astype(jnp.float32)  # (block_d, block_d)

    rows = jax.lax.broadcasted_iota(jnp.int32, t.shape, 0).astype(jnp.float32)
    mask = rows < cc
    valid = jnp.where(mask, t, 0.0)
    n_b_safe = jnp.maximum(cc, 1.0)
    mean_b = jnp.sum(valid, axis=0, keepdims=True) / n_b_safe  # (1, block_d)
    cent = jnp.where(mask, t - mean_b, 0.0)
    m2_b = jax.lax.dot_general(  # centᵀ·cent — the MXU-shaped reduction
        cent, cent, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )

    n_safe = jnp.maximum(n_a + cc, 1.0)
    delta = mean_b - mean0  # (1, block_d)
    mean_new = mean0 + delta * (cc / n_safe)
    outer = jax.lax.dot_general(  # δᵀ·δ from the (1, d) row vector
        delta, delta, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )
    m2_new = m2_0 + m2_b + outer * (n_a * cc / n_safe)

    upd = cc > 0.0  # empty chunk ⇒ state untouched
    mean_out_ref[...] = jnp.where(upd, mean_new, mean0)[None]
    m2_out_ref[...] = jnp.where(upd, m2_new, m2_0)[None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def online_update_kernel(
    chunk: jnp.ndarray,  # (M, Cp, dp) — C, d already padded (zeros)
    scalars: jnp.ndarray,  # (M, 1, 128) f32: col 0 = chunk count, col 1 = n_a
    mean: jnp.ndarray,  # (M, 1, dp)
    m2: jnp.ndarray,  # (M, dp, dp)
    *,
    interpret: bool = False,
):
    M, Cp, dp = chunk.shape
    return pl.pallas_call(
        _online_update_body,
        grid=(M,),
        in_specs=[
            pl.BlockSpec((1, Cp, dp), lambda m: (m, 0, 0)),
            pl.BlockSpec((1, 1, 128), lambda m: (m, 0, 0)),
            pl.BlockSpec((1, 1, dp), lambda m: (m, 0, 0)),
            pl.BlockSpec((1, dp, dp), lambda m: (m, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, dp), lambda m: (m, 0, 0)),
            pl.BlockSpec((1, dp, dp), lambda m: (m, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, 1, dp), jnp.float32),
            jax.ShapeDtypeStruct((M, dp, dp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(chunk, scalars, mean, m2)
