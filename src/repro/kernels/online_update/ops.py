"""jit'd public wrapper for the fused online-moments update: padding + dispatch.

Pads C to the sublane multiple and d to the lane multiple with zeros (both
are exactly moment-neutral: padded rows sit beyond the valid count and are
masked; padded features have zero mean/residual/δ so their mean/m2 entries
stay zero — sliced off on return). Falls back to the jnp reference for tiny
chunks where kernel launch overhead dominates.

Tolerance note (the ``online`` combiner's merge-rounding contract lives
here, next to the kernel): Welford merges associate differently across
chunkings *and* across evaluation orders, so the kernel agrees with
:func:`repro.kernels.online_update.ref.online_moments_update_ref` (and with
``combiners.online.online_update_chunk``) to f32 last-ulp per fold — the
centered Gram is one fused MXU matmul here vs an einsum there. Streams that
need a bitwise-vs-batch guarantee use the buffered combiners instead.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.online_update.kernel import online_update_kernel
from repro.kernels.online_update.ref import online_moments_update_ref


def _round_up(n: int, k: int) -> int:
    return (n + k - 1) // k * k


@functools.partial(jax.jit, static_argnames=("interpret", "min_kernel_c"))
def online_moments_update(
    count: jnp.ndarray,  # (M,)
    mean: jnp.ndarray,  # (M, d)
    m2: jnp.ndarray,  # (M, d, d)
    chunk: jnp.ndarray,  # (M, C, d)
    chunk_counts: Optional[jnp.ndarray] = None,  # (M,) valid prefix (None ⇒ C)
    *,
    interpret: bool | None = None,  # None -> repro.kernels.default_interpret()
    min_kernel_c: int = 32,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fold a dense ``(M, C, d)`` chunk into running ``(count, mean, m2)``."""
    if interpret is None:
        interpret = default_interpret()
    M, C, d = chunk.shape
    if C < min_kernel_c:
        return online_moments_update_ref(count, mean, m2, chunk, chunk_counts)
    cc = (
        jnp.full((M,), C, jnp.float32)
        if chunk_counts is None
        else chunk_counts.astype(jnp.float32)
    )
    Cp, dp = _round_up(C, 8), _round_up(d, 128)
    chunk_p = jnp.zeros((M, Cp, dp), jnp.float32).at[:, :C, :d].set(chunk)
    mean_p = jnp.zeros((M, 1, dp), jnp.float32).at[:, 0, :d].set(mean)
    m2_p = jnp.zeros((M, dp, dp), jnp.float32).at[:, :d, :d].set(m2)
    scalars = (
        jnp.zeros((M, 1, 128), jnp.float32)
        .at[:, 0, 0].set(cc)
        .at[:, 0, 1].set(count.astype(jnp.float32))
    )
    mean_o, m2_o = online_update_kernel(
        chunk_p, scalars, mean_p, m2_p, interpret=interpret
    )
    n_b = cc.astype(chunk.dtype)
    return count + n_b, mean_o[:, 0, :d], m2_o[:, :d, :d]
