"""Gaussian-product algebra for subposterior combination (paper Eqs. 3.1–3.2).

Everything here is Cholesky-based for numerical stability: subposterior sample
covariances can be poorly conditioned (thin posteriors at large shard sizes),
and the combination formulas multiply M precision matrices.

Two parameterizations are provided:

- full covariance ``(d, d)`` — used by the paper's experiments (d ≤ ~100);
- diagonal covariance ``(d,)`` — used for the LM-scale parametric combiner
  (d up to 10^9 parameters, where a dense ``(d, d)`` is impossible and the
  BvM regime makes the diagonal approximation standard practice).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

# a host-side constant, NOT jnp.log(...): importing this module must not run
# a JAX computation — jax.distributed.initialize() (repro.api.launch) refuses
# to start after one, and import must stay launch-safe
_LOG2PI = math.log(2.0 * math.pi)


class GaussianMoments(NamedTuple):
    """First two moments of a (sub)posterior sample set."""

    mean: jnp.ndarray  # (d,)
    cov: jnp.ndarray  # (d, d) or (d,) when diagonal


def fit_moments(
    samples: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    *,
    diag: bool = False,
    jitter: float = 1e-8,
) -> GaussianMoments:
    """Sample mean/covariance of ``samples`` ``(T, d)``.

    ``mask`` (T,) marks valid rows (ragged T_m support — straggler chains
    contribute fewer samples, paper footnote 1). Covariance uses the unbiased
    1/(T-1) normalizer and is jittered for downstream Cholesky stability.
    """
    samples = jnp.asarray(samples)
    T, d = samples.shape
    if mask is None:
        n = jnp.asarray(T, samples.dtype)
        mean = jnp.mean(samples, axis=0)
        centered = samples - mean
    else:
        mask = mask.astype(samples.dtype)
        n = jnp.maximum(jnp.sum(mask), 2.0)
        mean = jnp.sum(samples * mask[:, None], axis=0) / n
        centered = (samples - mean) * mask[:, None]
    denom = jnp.maximum(n - 1.0, 1.0)
    if diag:
        var = jnp.sum(centered**2, axis=0) / denom + jitter
        return GaussianMoments(mean=mean, cov=var)
    cov = jnp.matmul(centered.T, centered, precision=jax.lax.Precision.HIGHEST) / denom
    cov = cov + jitter * jnp.eye(d, dtype=samples.dtype)
    return GaussianMoments(mean=mean, cov=cov)


def _chol_inverse(cov: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Return (precision, chol(cov)) via Cholesky."""
    chol = jnp.linalg.cholesky(cov)
    eye = jnp.eye(cov.shape[-1], dtype=cov.dtype)
    inv = jax.scipy.linalg.cho_solve((chol, True), eye)
    return inv, chol


def product_moments(
    means: jnp.ndarray, covs: jnp.ndarray, *, jitter: float = 1e-10
) -> GaussianMoments:
    """Moments of ``∏_m N(θ | μ_m, Σ_m)`` — paper Eqs. 3.1 / 3.2.

    means ``(M, d)``, covs ``(M, d, d)``. Computed in precision space with
    Cholesky solves; never forms an explicit matrix inverse of Σ̂_M.
    """
    d = means.shape[-1]

    def precision_and_weighted_mean(mu, cov):
        prec, _ = _chol_inverse(cov)
        return prec, jnp.matmul(prec, mu, precision=jax.lax.Precision.HIGHEST)

    precs, wmeans = jax.vmap(precision_and_weighted_mean)(means, covs)
    lam = jnp.sum(precs, axis=0) + jitter * jnp.eye(d, dtype=means.dtype)
    eta = jnp.sum(wmeans, axis=0)
    chol_lam = jnp.linalg.cholesky(lam)
    mean = jax.scipy.linalg.cho_solve((chol_lam, True), eta)
    cov = jax.scipy.linalg.cho_solve((chol_lam, True), jnp.eye(d, dtype=means.dtype))
    # Symmetrize: cho_solve output drifts slightly off-symmetric in fp32.
    cov = 0.5 * (cov + cov.T)
    return GaussianMoments(mean=mean, cov=cov)


def product_moments_diag(means: jnp.ndarray, variances: jnp.ndarray) -> GaussianMoments:
    """Diagonal-covariance version of :func:`product_moments`.

    means/variances ``(M, d)``. This is the LM-scale path: O(M·d) memory, maps
    cleanly onto a sharded ``d`` axis (each TP shard combines its slice
    independently — the combination itself is embarrassingly parallel in d).
    """
    precs = 1.0 / variances
    lam = jnp.sum(precs, axis=0)
    mean = jnp.sum(precs * means, axis=0) / lam
    return GaussianMoments(mean=mean, cov=1.0 / lam)


def sample_gaussian(
    key: jax.Array, moments: GaussianMoments, n: int
) -> jnp.ndarray:
    """Draw ``n`` samples from N(mean, cov); cov may be full or diagonal."""
    d = moments.mean.shape[-1]
    eps = jax.random.normal(key, (n, d), dtype=moments.mean.dtype)
    if moments.cov.ndim == 1:
        return moments.mean + eps * jnp.sqrt(moments.cov)
    chol = jnp.linalg.cholesky(moments.cov)
    return moments.mean + eps @ chol.T


def log_normal_pdf(
    x: jnp.ndarray, mean: jnp.ndarray, cov: jnp.ndarray
) -> jnp.ndarray:
    """log N(x | mean, cov) with full ``(d,d)`` or diagonal ``(d,)`` cov.

    Broadcasts over leading dims of ``x``.
    """
    d = x.shape[-1]
    diff = x - mean
    if cov.ndim == 1:
        quad = jnp.sum(diff**2 / cov, axis=-1)
        logdet = jnp.sum(jnp.log(cov))
    else:
        chol = jnp.linalg.cholesky(cov)
        batch_shape = diff.shape[:-1]
        flat = diff.reshape(-1, d).T  # (d, B)
        sol = jax.scipy.linalg.solve_triangular(chol, flat, lower=True)
        quad = jnp.sum(sol**2, axis=0).reshape(batch_shape)
        logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(chol)))
    return -0.5 * (quad + logdet + d * _LOG2PI)


def log_isotropic_normal_pdf(
    x: jnp.ndarray, mean: jnp.ndarray, var: jnp.ndarray | float
) -> jnp.ndarray:
    """log N(x | mean, var·I). ``var`` is a scalar; broadcasts over leading dims."""
    d = x.shape[-1]
    sq = jnp.sum((x - mean) ** 2, axis=-1)
    return -0.5 * (sq / var + d * (jnp.log(var) + _LOG2PI))
