"""jnp reference for the fused Welford/Chan-merge update (the test oracle).

Mirrors ``repro.core.combiners.online.online_update_chunk`` on raw arrays
(the kernels layer stays independent of the combiner registry): a dense
``(M, C, d)`` chunk is reduced to per-machine batch moments and Chan-merged
into the running ``(count, mean, m2)`` state. Invalid rows (beyond each
machine's ``chunk_counts`` prefix) are excluded with ``where``, never
mask-multiplied — 0·NaN would leak.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def online_moments_update_ref(
    count: jnp.ndarray,  # (M,)
    mean: jnp.ndarray,  # (M, d)
    m2: jnp.ndarray,  # (M, d, d)
    chunk: jnp.ndarray,  # (M, C, d)
    chunk_counts: Optional[jnp.ndarray] = None,  # (M,) valid prefix (None ⇒ C)
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    M, C, _ = chunk.shape
    cc = (
        jnp.full((M,), C, jnp.int32)
        if chunk_counts is None
        else chunk_counts.astype(jnp.int32)
    )
    mask = (jnp.arange(C)[None, :] < cc[:, None])[..., None]  # (M, C, 1)
    n_b = cc.astype(chunk.dtype)
    n_b_safe = jnp.maximum(n_b, 1.0)
    valid = jnp.where(mask, chunk, 0.0)
    mean_b = jnp.sum(valid, axis=1) / n_b_safe[:, None]  # (M, d)
    cent = jnp.where(mask, chunk - mean_b[:, None, :], 0.0)
    m2_b = jnp.einsum(  # (M, d, d)
        "mci,mcj->mij", cent, cent, precision=jax.lax.Precision.HIGHEST
    )

    n_a = count
    n = n_a + n_b
    n_safe = jnp.maximum(n, 1.0)
    delta = mean_b - mean
    mean_new = mean + delta * (n_b / n_safe)[:, None]
    outer = jnp.einsum(
        "mi,mj->mij", delta, delta, precision=jax.lax.Precision.HIGHEST
    )
    m2_new = m2 + m2_b + outer * (n_a * n_b / n_safe)[:, None, None]
    upd = (n_b > 0)[:, None]
    return (
        n,
        jnp.where(upd, mean_new, mean),
        jnp.where(upd[..., None], m2_new, m2),
    )
