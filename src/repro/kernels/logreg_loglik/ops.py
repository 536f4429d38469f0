"""Public entry points: the lane-dense layout, the fused op, and when to use it."""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.logreg_loglik.kernel import logreg_loglik_grad_kernel

# Fewer rows than this stay on the jnp form: the one-row padding correction,
# SGLD's 256-row minibatches, anything XLA reads in a few microseconds.
MIN_KERNEL_N = 1024
SUB = 512  # lanes the kernel body takes at a time (a multiple of 128)
BLOCK_BYTES = 4 << 20  # one (d, block_n) float32 block of ut, at most


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_fused(n_rows: int) -> bool:
    """Whether a likelihood over ``n_rows`` rows takes the fused kernel: on a
    TPU, for at least :data:`MIN_KERNEL_N` rows."""
    return on_tpu() and n_rows >= MIN_KERNEL_N


def lane_dense(x: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    """The kernel's layout of a shard: ``(s ⊙ X)ᵀ``, (d, N) float32, from
    ``x`` (N, d) and signs ``s`` (N,) = 2y − 1. The product is exact."""
    return (x.astype(jnp.float32) * s.astype(jnp.float32)[:, None]).T


def _block_n(n: int, d: int) -> int:
    """Lanes a grid step reads: the whole shard when it fits
    :data:`BLOCK_BYTES` (rounded up to :data:`SUB`), else the most that do."""
    d_pad = -(-d // 8) * 8
    most = max(SUB, BLOCK_BYTES // (4 * d_pad) // SUB * SUB)
    return min(-(-n // SUB) * SUB, most)


@functools.partial(jax.jit, static_argnames=("interpret",))
def logreg_loglik_grad(
    ut: jnp.ndarray,  # (d, N): lane_dense(X, s)
    beta: jnp.ndarray,  # (d,)
    *,
    interpret: Optional[bool] = None,  # None -> repro.kernels.default_interpret()
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused ``(ℓ (), ∇ℓ (d,))`` of the logistic likelihood in one pass over
    ``ut``; ``ref.py`` on the row layout is its reference. Chains batch by
    ``vmap``."""
    if interpret is None:
        interpret = default_interpret()
    d, n = ut.shape
    loglik, grad = logreg_loglik_grad_kernel(
        ut, beta.astype(jnp.float32)[:, None],
        block_n=_block_n(n, d), sub=SUB, interpret=interpret,
    )
    return loglik[0, 0], grad[:, 0]
