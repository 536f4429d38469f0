"""Pallas TPU kernel: batched all-machines streaming Gaussian-KDE log-density.

Flash-attention-style online logsumexp, rethought for KDE scoring:

- grid = (nq // block_q, M, T // block_s): parallel over query tiles,
  sequential over machines and over each machine's center tiles.
- Per step: squared distances via the MXU identity
      ‖q − s‖² = ‖q‖² + ‖s‖² − 2·s·q
  (one (block_s, d)·(d, block_q) matmul — the same trick flash attention
  uses to keep the QKᵀ score tile MXU-bound), then an online max/renormalize
  update of the running (m, ℓ) pair in VMEM scratch. The (M, nq, T) score
  tensor never exists in HBM.
- Queries arrive transposed, ``(d, nq)``, so every per-query quantity (the
  running max and sum, the epilogue accumulators, the output rows) is a
  lane-dense ``(1, block_q)`` row: reductions run over the sublane (center)
  axis and the outputs are ``(8, 128)``-tileable without a relayout.

VMEM per step: (block_q + block_s)·d·4 + 2·block_q·block_s·4 + O(block_q).
Defaults (256, 512, d ≤ 1024) stay well under 16 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_BIG = -1e30


def _machine_kde_kernel(
    scale_ref,  # scalar-prefetch: (M,) 1 / (2·h_m²)
    c_ref,  # scalar-prefetch: (M,) int32 valid-prefix counts
    norm_ref,  # scalar-prefetch: (M,) log normalizer log(n_m) + (d/2)·log(2π h_m²)
    w_ref,  # scalar-prefetch: (M,) log mixture weights (mixture epilogues)
    q_ref,  # (d, block_q) transposed query tile
    s_ref,  # (1, block_s, d) center tile of machine m
    *refs,  # out refs (by `reduce`), then scratch: m, l, acc, mx_m, mx_l
    n_sblocks: int,
    n_machines: int,
    block_s: int,
    reduce: str,
):
    outs, (m_scr, l_scr, acc_scr, mxm_scr, mxl_scr) = refs[:-5], refs[-5:]
    m = pl.program_id(1)
    j = pl.program_id(2)
    first_machine = m == 0
    last_machine = m == n_machines - 1

    @pl.when(j == 0)
    def _init_machine():
        m_scr[...] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[...] = jnp.zeros_like(l_scr)

    @pl.when(jnp.logical_and(first_machine, j == 0))
    def _init_epilogue():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        mxm_scr[...] = jnp.full_like(mxm_scr, _NEG_BIG)
        mxl_scr[...] = jnp.zeros_like(mxl_scr)

    qt = q_ref[...].astype(jnp.float32)  # (d, block_q)
    s = s_ref[0].astype(jnp.float32)  # (block_s, d)
    cnt = c_ref[m]

    qn = jnp.sum(qt * qt, axis=0, keepdims=True)  # (1, block_q)
    sn = jnp.sum(s * s, axis=1, keepdims=True)  # (block_s, 1)
    cross = jax.lax.dot_general(
        s, qt, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (block_s, block_q)
    scores = -(qn + sn - 2.0 * cross) * scale_ref[m]

    # valid-prefix mask lives IN the kernel: center row t of tile j is row
    # j·block_s + t of machine m's chain. A where-select (not an additive
    # mask) so NaN garbage beyond counts[m] can never poison max/exp.
    row = jax.lax.broadcasted_iota(jnp.int32, (block_s, 1), 0) + j * block_s
    valid = row < cnt  # (block_s, 1)
    scores = jnp.where(valid, scores, _NEG_BIG)

    m_new = jnp.maximum(m_scr[...], jnp.max(scores, axis=0, keepdims=True))
    p = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
    l_scr[...] = l_scr[...] * jnp.exp(m_scr[...] - m_new) + jnp.sum(
        p, axis=0, keepdims=True
    )
    m_scr[...] = m_new

    @pl.when(j == n_sblocks - 1)
    def _finalize_machine():
        lpm = m_scr[...] + jnp.log(l_scr[...]) - norm_ref[m]  # (1, block_q); -inf if empty

        if reduce == "none":
            outs[0][0] = lpm
            return

        k = 0
        if reduce in ("product", "product_mixture"):
            acc_scr[...] = acc_scr[...] + lpm  # Σ_m log p̂_m; -inf propagates

            @pl.when(last_machine)
            def _():
                outs[0][...] = acc_scr[...]

            k = 1
        if reduce in ("mixture", "product_mixture"):
            # online logsumexp across machines of log w_m + log p̂_m; empty
            # machines enter as the -1e30 sentinel and contribute exp→0.
            lw = jnp.maximum(lpm + w_ref[m], _NEG_BIG)
            mx_new = jnp.maximum(mxm_scr[...], lw)
            pm = jnp.where(lw > 0.1 * _NEG_BIG, jnp.exp(lw - mx_new), 0.0)
            mxl_scr[...] = mxl_scr[...] * jnp.exp(mxm_scr[...] - mx_new) + pm
            mxm_scr[...] = mx_new

            @pl.when(last_machine)
            def _():
                outs[k][...] = mxm_scr[...] + jnp.log(mxl_scr[...])


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_s", "interpret", "reduce"),
)
def machine_kde_log_density_kernel(
    queries_t: jnp.ndarray,  # (d, nq) transposed, padded: nq % block_q == 0
    samples: jnp.ndarray,  # (M, T, d) padded: T % block_s == 0
    scale: jnp.ndarray,  # (M,) float32 1 / (2·h_m²)
    counts: jnp.ndarray,  # (M,) int32 valid-prefix counts (≤ unpadded T)
    log_norm: jnp.ndarray,  # (M,) float32 per-machine log normalizer
    log_mix_w: jnp.ndarray,  # (M,) float32 log mixture weights
    *,
    reduce: str = "none",
    block_q: int = 256,
    block_s: int = 512,
    interpret: bool = False,
):
    """All-machines KDE scoring in ONE launch: grid (q-tile, machine, s-tile).

    Flash-style online logsumexp per (query-tile, machine) in VMEM scratch —
    the (M, nq, T) score tensor never exists. ``reduce`` selects the fused
    epilogue: ``"none"`` → (M, nq) per-machine log densities; ``"product"`` →
    (nq,) pooled product score Σ_m log p̂_m; ``"mixture"`` → (nq,) mixture
    score logsumexp_m(log w_m + log p̂_m); ``"product_mixture"`` → both, with
    the (M, nq) matrix never materialized in any reduced mode. Per-machine
    bandwidth scale, log normalizer and valid-prefix ``counts`` ride the
    scalar-prefetch operands and are applied inside the kernel, so dense and
    ragged chains take the same code path (a machine's rows beyond
    ``counts[m]`` may hold NaN garbage — they are where-selected out before
    any max/exp). The normalizer comes in precomputed, by the reference's own
    expression: ``log`` on a TPU is an approximation (about 1e-4 absolute
    in a v5e kernel), and (d/2)·log h multiplies its error by d/2.

    Outputs are written as ``(M, 1, nq)`` / ``(1, nq)`` slabs of lane-dense
    ``(1, block_q)`` rows (the last two block dims then equal the array's or
    tile by 128) and reshaped to (M, nq) / (nq,) here.
    """
    d, nq = queries_t.shape
    M, T, _ = samples.shape
    n_q, n_s = nq // block_q, T // block_s
    if reduce == "none":
        n_out, out_shape = 1, (M, 1, nq)
        out_block = pl.BlockSpec((1, 1, block_q), lambda i, m, j, *_: (m, 0, i))
    elif reduce in ("product", "mixture", "product_mixture"):
        n_out, out_shape = 1 + (reduce == "product_mixture"), (1, nq)
        out_block = pl.BlockSpec((1, block_q), lambda i, m, j, *_: (0, i))
    else:
        raise ValueError(f"unknown reduce={reduce!r}")

    kernel = functools.partial(
        _machine_kde_kernel,
        n_sblocks=n_s, n_machines=M, block_s=block_s, reduce=reduce,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_q, M, n_s),
        in_specs=[
            pl.BlockSpec((d, block_q), lambda i, m, j, *_: (0, i)),
            pl.BlockSpec((1, block_s, d), lambda i, m, j, *_: (m, j, 0)),
        ],
        out_specs=[out_block] * n_out,
        scratch_shapes=[pltpu.VMEM((1, block_q), jnp.float32) for _ in range(5)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(out_shape, jnp.float32)] * n_out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(
        scale.astype(jnp.float32),
        counts.astype(jnp.int32),
        log_norm.astype(jnp.float32),
        log_mix_w.astype(jnp.float32),
        queries_t,
        samples,
    )
    if reduce == "none":
        return out[0].reshape(M, nq)
    out = tuple(o.reshape(nq) for o in out)
    return out[0] if n_out == 1 else out
