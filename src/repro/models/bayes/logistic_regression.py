"""Bayesian logistic regression — paper §8.1.

Synthetic data matches §8.1.1: each element of β and X drawn standard normal,
y_i ~ Bernoulli(logit⁻¹(X_i β)), N=50,000, d=50 (no intercept, per footnote 6).
The covtype task (§8.1.2) is emulated by :func:`generate_covtype_like` —
581,012×54 with a correlated design and class imbalance — since the real
dataset is not available offline; benchmarks report the same accuracy-vs-time
protocol.

θ = β ∈ R^d (already unconstrained — paper §6 scope).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.logreg_loglik import lane_dense, logreg_loglik_grad, use_fused
from repro.models.bayes import registry

Data = Dict[str, jnp.ndarray]


def generate_data(
    key: jax.Array, n: int = 50_000, d: int = 50, dtype=jnp.float32
) -> Tuple[Data, jnp.ndarray]:
    """§8.1.1 synthetic set: X, β ~ N(0,1) elementwise; y ~ Bern(σ(Xβ))."""
    k_beta, k_x, k_y = jax.random.split(key, 3)
    beta = jax.random.normal(k_beta, (d,), dtype)
    x = jax.random.normal(k_x, (n, d), dtype)
    logits = x @ beta
    y = jax.random.bernoulli(k_y, jax.nn.sigmoid(logits)).astype(dtype)
    return {"x": x, "y": y}, beta


def generate_covtype_like(
    key: jax.Array, n: int = 581_012, d: int = 54, dtype=jnp.float32
) -> Tuple[Data, jnp.ndarray]:
    """Covtype stand-in: correlated features, heavier class imbalance."""
    k_beta, k_x, k_mix, k_y = jax.random.split(key, 4)
    beta = jax.random.normal(k_beta, (d,), dtype) * 0.5
    base = jax.random.normal(k_x, (n, d), dtype)
    mixer = jax.random.normal(k_mix, (d, d), dtype) * (0.3 / jnp.sqrt(d))
    x = base + base @ mixer  # mildly correlated design
    logits = x @ beta - 0.8  # imbalance
    y = jax.random.bernoulli(k_y, jax.nn.sigmoid(logits)).astype(dtype)
    return {"x": x, "y": y}, beta


def log_prior(theta: jnp.ndarray, sigma: float = 5.0) -> jnp.ndarray:
    """β ~ N(0, σ² I) — weakly informative (Stan default-style)."""
    d = theta.shape[-1]
    return -0.5 * jnp.sum(theta**2) / sigma**2 - 0.5 * d * jnp.log(
        2.0 * jnp.pi * sigma**2
    )


def _log_lik_jnp(theta: jnp.ndarray, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    s = 2.0 * y - 1.0
    return jnp.sum(jax.nn.log_sigmoid(s * (x @ theta)))


@jax.custom_vjp
def _log_lik_fused(theta, x, y, ut):
    return _log_lik_jnp(theta, x, y)


def _fused_fwd(theta, x, y, ut):
    return logreg_loglik_grad(ut, theta)  # ℓ, and ∇ℓ kept for the backward


def _fused_bwd(grad, ct):
    return ct * grad, None, None, None  # the data take no cotangent


_log_lik_fused.defvjp(_fused_fwd, _fused_bwd)


def shard_form(shard: Data) -> Data:
    """The shard with its lane-dense copy ``ut = (s ⊙ X)ᵀ`` for the fused
    kernel (``BayesModel.shard_form``)."""
    return {**shard, "ut": lane_dense(shard["x"], 2.0 * shard["y"] - 1.0)}


def log_lik(theta: jnp.ndarray, data: Data) -> jnp.ndarray:
    """Σ_i log p(y_i | x_i, β) = Σ_i log σ(s_i · x_i β) with s_i = 2y_i − 1.

    The value is always this jnp form, the CPU path and the reference. On a
    :func:`shard_form` (made where ``repro.kernels.logreg_loglik.use_fused``
    holds: a TPU, a shard of at least ``MIN_KERNEL_N`` rows) the gradient
    comes with the value from one pass of the fused Pallas kernel over the
    lane-dense copy: ``value_and_grad`` (MALA, HMC) reads X once, not twice.
    """
    if "ut" in data:
        return _log_lik_fused(theta, data["x"], data["y"], data["ut"])
    return _log_lik_jnp(theta, data["x"], data["y"])


def predictive_accuracy(
    betas: jnp.ndarray, x: jnp.ndarray, y: jnp.ndarray, *, chunk: int = 1024
) -> jnp.ndarray:
    """§8.1.2 posterior-predictive classification accuracy.

    P(y|x) ≈ (1/S) Σ_s σ(xᵀβ_s); predict the argmax class.
    """
    n = x.shape[0]
    pad = (-n) % chunk
    xp = jnp.pad(x, ((0, pad), (0, 0)))

    def block(xc):
        probs = jnp.mean(jax.nn.sigmoid(xc @ betas.T), axis=1)
        return probs

    probs = jax.lax.map(block, xp.reshape(-1, chunk, x.shape[1])).reshape(-1)[:n]
    return jnp.mean((probs > 0.5).astype(jnp.float32) == y)


registry.register_model(
    registry.BayesModel(
        name="logreg",
        generate_data=generate_data,
        log_prior=log_prior,
        log_lik=log_lik,
        d=50,
        default_n=50_000,
        default_sampler="mala",
        fused_rows=use_fused,
        shard_form=shard_form,
    ),
    "logistic_regression",
)

registry.register_model(
    registry.BayesModel(
        name="covtype",
        generate_data=lambda key, n=581_012: generate_covtype_like(key, n),
        log_prior=log_prior,
        log_lik=log_lik,
        d=54,
        default_n=581_012,
        default_sampler="mala",
        fused_rows=use_fused,
        shard_form=shard_form,
    )
)
