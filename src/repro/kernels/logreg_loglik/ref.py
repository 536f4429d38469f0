"""Pure-jnp oracle: fused logistic-regression log-likelihood + gradient.

The paper's per-machine sampler (§8.1) spends its time in exactly this O(N·d)
reduction every MH/HMC step:

    ℓ(β)  = Σ_i log σ(y_i · x_i·β)          (y ∈ {−1, +1})
    ∇ℓ(β) = Σ_i y_i · σ(−y_i · x_i·β) · x_i

``scale`` multiplies both (the subposterior's N_m/B minibatch factor).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def logreg_loglik_grad_ref(
    X: jnp.ndarray,  # (N, d)
    y: jnp.ndarray,  # (N,) in {-1, +1}
    beta: jnp.ndarray,  # (d,)
    *,
    scale: float | jnp.ndarray = 1.0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    X = X.astype(jnp.float32)
    y = y.astype(jnp.float32)
    beta = beta.astype(jnp.float32)
    z = y * jnp.matmul(X, beta, precision=jax.lax.Precision.HIGHEST)  # (N,)
    loglik = jnp.sum(jax.nn.log_sigmoid(z))
    coeff = y * jax.nn.sigmoid(-z)  # (N,)
    grad = jnp.matmul(X.T, coeff, precision=jax.lax.Precision.HIGHEST)
    return scale * loglik, scale * grad
