"""Pallas TPU flash-attention forward — the §Perf lever for the train/prefill
memory term (EXPERIMENTS.md: XLA-lowered flash streams every (q_blk × kv_blk)
f32 score tile through HBM; this kernel keeps them in VMEM).

TPU-native design:

- grid = (B·K, S/block_q, T/block_k): batch×kv-head program axis and q-tile
  axis are ``parallel``; the kv axis is ``arbitrary`` (sequential online-
  softmax accumulation — the FlashAttention-2 loop order).
- One program instance owns one kv-head's G query heads: the q tile loads as
  (block_q, G·hd) and is reshaped to (block_q·G, hd) so the score matmul
  (block_q·G, hd)·(hd, block_k) and the PV matmul run as plain MXU GEMMs —
  GQA grouping costs zero extra traffic.
- VMEM scratch carries the running (m, ℓ, acc) across kv steps; the output
  tile is written once, on the last kv block (single HBM write per tile).
- Causal tiles wholly above the diagonal are skipped via ``pl.when`` (the
  classic 2× saving); kv-tail padding is masked with −∞ from ``kv_len``.

VMEM at defaults (block_q=512, block_k=512, G≤8, hd=128, f32 scratch):
q 512·8·128·4 ≈ 2 MB, k/v 512·128·4 ≈ 0.25 MB each, scores 4096·512·4 ≈ 8 MB
— fits the 16 MB/core budget; ops.py shrinks blocks when G·hd is larger.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _flash_fwd_kernel(
    q_ref,  # (1, block_q, G*hd)
    k_ref,  # (1, block_k, hd)
    v_ref,  # (1, block_k, hd_v)
    out_ref,  # (1, block_q, G*hd_v)
    m_ref,  # (block_q*G,) scratch
    l_ref,  # (block_q*G,) scratch
    acc_ref,  # (block_q*G, hd_v) scratch
    *,
    n_kv: int,
    block_q: int,
    block_k: int,
    g: int,
    hd: int,
    hd_v: int,
    kv_len: int,
    causal: bool,
):
    jq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _step():
        q = q_ref[0].astype(jnp.float32).reshape(block_q, g, hd)
        q = q.transpose(1, 0, 2).reshape(g * block_q, hd)  # head-major rows
        k = k_ref[0].astype(jnp.float32)  # (block_k, hd)
        v = v_ref[0].astype(jnp.float32)  # (block_k, hd_v)
        scale = hd ** -0.5
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (g*block_q, block_k)

        kv_pos = jk * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        valid = kv_pos < kv_len
        if causal:
            q_pos = jq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (g * block_q, 1), 0
            ) % block_q
            valid = valid & (q_pos >= kv_pos)
        scores = jnp.where(valid, scores, _NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    if causal:
        # skip tiles strictly above the causal diagonal (the classic 2×)
        pl.when(jk * block_k <= jq * block_q + block_q - 1)(_step)
    else:
        _step()

    @pl.when(jk == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        out = (acc_ref[...] / denom).reshape(g, block_q, hd_v)
        out = out.transpose(1, 0, 2).reshape(block_q, g * hd_v)
        out_ref[0] = out.astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_k", "causal", "kv_len", "g", "hd", "hd_v", "interpret"),
)
def flash_attention_fwd_kernel(
    q: jnp.ndarray,  # (BK, S, G*hd) padded: S % block_q == 0
    k: jnp.ndarray,  # (BK, T, hd)   padded: T % block_k == 0
    v: jnp.ndarray,  # (BK, T, hd_v)
    *,
    g: int,
    hd: int,
    hd_v: int,
    kv_len: int,  # true T before padding
    causal: bool = True,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    bk, s, _ = q.shape
    t = k.shape[1]
    n_q, n_kv = s // block_q, t // block_k
    kernel = functools.partial(
        _flash_fwd_kernel,
        n_kv=n_kv, block_q=block_q, block_k=block_k,
        g=g, hd=hd, hd_v=hd_v, kv_len=kv_len, causal=causal,
    )
    return pl.pallas_call(
        kernel,
        grid=(bk, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, g * hd), lambda i, jq, jk: (i, jq, 0)),
            pl.BlockSpec((1, block_k, hd), lambda i, jq, jk: (i, jk, 0)),
            pl.BlockSpec((1, block_k, hd_v), lambda i, jq, jk: (i, jk, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, g * hd_v), lambda i, jq, jk: (i, jq, 0)),
        out_shape=jax.ShapeDtypeStruct((bk, s, g * hd_v), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q * g,), jnp.float32),
            pltpu.VMEM((block_q * g,), jnp.float32),
            pltpu.VMEM((block_q * g, hd_v), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
