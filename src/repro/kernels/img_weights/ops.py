"""jit'd public wrapper for the IMG log-weight kernel: padding + dispatch.

Pads P to the block multiple (extra rows sliced off) and d with zeros (zero
features are exactly weight-neutral: they shift SSE by 0). Falls back to the
reference for tiny problems where kernel launch overhead dominates.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.img_weights.kernel import img_log_weights_kernel
from repro.kernels.img_weights.ref import img_log_weights_ref


def _round_up(n: int, k: int) -> int:
    return (n + k - 1) // k * k


@functools.partial(
    jax.jit, static_argnames=("block_p", "block_d", "interpret", "min_kernel_p")
)
def img_log_weights(
    theta: jnp.ndarray,  # (P, M, d)
    h: jnp.ndarray | float,
    *,
    block_p: int = 256,
    block_d: int = 512,
    interpret: bool | None = None,  # None -> repro.kernels.default_interpret()
    min_kernel_p: int = 64,
) -> jnp.ndarray:
    if interpret is None:
        interpret = default_interpret()
    P, M, d = theta.shape
    if P < min_kernel_p:
        return img_log_weights_ref(theta, h)
    block_p = min(block_p, _round_up(P, 8))
    block_d = min(block_d, _round_up(d, 128))
    Pp, dp = _round_up(P, block_p), _round_up(d, block_d)
    padded = jnp.zeros((Pp, M, dp), theta.dtype).at[:P, :, :d].set(theta)
    h_arr = jnp.asarray(h, jnp.float32).reshape(1)
    out = img_log_weights_kernel(
        padded, h_arr, block_p=block_p, block_d=block_d, interpret=interpret
    )
    # the log-normalizer, by the reference's expression and outside the
    # kernel: M·(d/2) multiplies the error of the TPU's approximate ``log``
    h32 = jnp.asarray(h, jnp.float32)
    return out[:P] - M * (d / 2.0) * jnp.log(2.0 * jnp.pi * h32 * h32)
