"""Evaluation metrics — the paper's L2 density distance (§8) + ESS/MMD.

The paper measures ``d₂(p, p̂) = ‖p − p̂‖₂`` between the groundtruth posterior
and a proposed posterior, both represented by samples. With Gaussian-KDE
density estimates this has a *closed form* in the kernel cross-terms (no grid):

  ‖p̂ − q̂‖₂² = 1/T² ΣΣ N(xᵢ−xⱼ | 0, 2h₁²I) + 1/S² ΣΣ N(yᵢ−yⱼ | 0, 2h₂²I)
              − 2/(TS) ΣΣ N(xᵢ−yⱼ | 0, (h₁²+h₂²)I)

Each double sum is a pairwise-Gaussian reduction — the exact computation the
``repro.kernels.kde_density`` Pallas kernel tiles (flash-style streaming
logsumexp, no (T,S) matrix in HBM). The jnp implementation here is chunked so
CPU tests stay in memory.
"""

from __future__ import annotations

import math

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import bandwidth as bw

# host-side, not jnp.log(...): module import must not run a JAX
# computation (jax.distributed.initialize refuses to start after one)
_LOG2PI = math.log(2.0 * math.pi)


def log_mean_gaussian_cross(
    x: jnp.ndarray, y: jnp.ndarray, var: jnp.ndarray | float, *, chunk: int = 512
) -> jnp.ndarray:
    """log [ 1/(TS) ΣΣ N(xᵢ − yⱼ | 0, var·I) ] computed in row chunks.

    x ``(T, d)``, y ``(S, d)``. Stable via a single global logsumexp performed
    over per-chunk partial logsumexps.
    """
    T, d = x.shape
    S = y.shape[0]
    var = jnp.asarray(var, x.dtype)
    log_norm = -0.5 * d * (jnp.log(var) + _LOG2PI)
    pad = (-T) % chunk
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    valid = jnp.pad(jnp.ones((T,), x.dtype), (0, pad))
    xb = xp.reshape(-1, chunk, d)
    vb = valid.reshape(-1, chunk)

    def row_block(xc, vc):
        # (chunk, S) squared distances via ‖x‖² + ‖y‖² − 2x·y
        sq = (
            jnp.sum(xc**2, -1)[:, None]
            + jnp.sum(y**2, -1)[None, :]
            - 2.0 * jnp.matmul(xc, y.T, precision=jax.lax.Precision.HIGHEST)
        )
        logk = -0.5 * sq / var
        block_lse = jax.scipy.special.logsumexp(logk, axis=(0, 1), b=vc[:, None])
        return block_lse

    block_lses = jax.lax.map(lambda args: row_block(*args), (xb, vb))
    total = jax.scipy.special.logsumexp(block_lses)
    return total + log_norm - jnp.log(jnp.asarray(T * S, x.dtype))


def l2_distance(
    p_samples: jnp.ndarray,
    q_samples: jnp.ndarray,
    *,
    h_p: Optional[float] = None,
    h_q: Optional[float] = None,
    chunk: int = 512,
) -> jnp.ndarray:
    """Paper's d₂(p, q) between two sample sets via Gaussian-KDE closed form.

    Bandwidths default to Silverman's rule per sample set.
    """
    hp = bw.silverman(p_samples) if h_p is None else jnp.asarray(h_p)
    hq = bw.silverman(q_samples) if h_q is None else jnp.asarray(h_q)
    t_pp = log_mean_gaussian_cross(p_samples, p_samples, 2.0 * hp**2, chunk=chunk)
    t_qq = log_mean_gaussian_cross(q_samples, q_samples, 2.0 * hq**2, chunk=chunk)
    t_pq = log_mean_gaussian_cross(p_samples, q_samples, hp**2 + hq**2, chunk=chunk)
    # ∫(p̂−q̂)² = e^{t_pp} + e^{t_qq} − 2 e^{t_pq}; do it in a stable scaled
    # space and return in LOG-SQRT form folded back at f64 precision — at
    # d≈50 the KDE normalizer (2πh²)^{−d/2} overflows f32 (paper §8.1.3
    # plots exactly this regime).
    m = jnp.maximum(jnp.maximum(t_pp, t_qq), t_pq)
    val = jnp.exp(t_pp - m) + jnp.exp(t_qq - m) - 2.0 * jnp.exp(t_pq - m)
    log_d2 = 0.5 * (jnp.log(jnp.maximum(val, 1e-38)) + m)
    return jnp.exp(log_d2)  # may overflow f32 beyond d≈40 — use log_l2_distance


def log_l2_distance(
    p_samples: jnp.ndarray,
    q_samples: jnp.ndarray,
    *,
    h_p: Optional[float] = None,
    h_q: Optional[float] = None,
    chunk: int = 512,
) -> jnp.ndarray:
    """log d₂(p, q) — overflow-proof form for high-d comparisons."""
    hp = bw.silverman(p_samples) if h_p is None else jnp.asarray(h_p)
    hq = bw.silverman(q_samples) if h_q is None else jnp.asarray(h_q)
    t_pp = log_mean_gaussian_cross(p_samples, p_samples, 2.0 * hp**2, chunk=chunk)
    t_qq = log_mean_gaussian_cross(q_samples, q_samples, 2.0 * hq**2, chunk=chunk)
    t_pq = log_mean_gaussian_cross(p_samples, q_samples, hp**2 + hq**2, chunk=chunk)
    m = jnp.maximum(jnp.maximum(t_pp, t_qq), t_pq)
    val = jnp.exp(t_pp - m) + jnp.exp(t_qq - m) - 2.0 * jnp.exp(t_pq - m)
    return 0.5 * (jnp.log(jnp.maximum(val, 1e-38)) + m)


def kde_logpdf(
    queries: jnp.ndarray, samples: jnp.ndarray, h: jnp.ndarray | float, *, chunk: int = 512
) -> jnp.ndarray:
    """log p̂(queries) under the Gaussian KDE of ``samples`` with bandwidth h.

    queries ``(Q, d)``, samples ``(T, d)`` → ``(Q,)``. Chunked over queries;
    Pallas-accelerated variant in ``repro.kernels.kde_density``.
    """
    Q, d = queries.shape
    T = samples.shape[0]
    h = jnp.asarray(h, queries.dtype)
    log_norm = -0.5 * d * (2.0 * jnp.log(h) + _LOG2PI) - jnp.log(jnp.asarray(T, queries.dtype))
    pad = (-Q) % chunk
    qp = jnp.pad(queries, ((0, pad), (0, 0))).reshape(-1, chunk, d)

    def block(qc):
        sq = (
            jnp.sum(qc**2, -1)[:, None]
            + jnp.sum(samples**2, -1)[None, :]
            - 2.0 * jnp.matmul(qc, samples.T, precision=jax.lax.Precision.HIGHEST)
        )
        return jax.scipy.special.logsumexp(-0.5 * sq / h**2, axis=1)

    out = jax.lax.map(block, qp).reshape(-1)[:Q]
    return out + log_norm


def effective_sample_size(chain: jnp.ndarray) -> jnp.ndarray:
    """ESS of a 1-d chain via FFT autocorrelation + Geyer initial positive pairs."""
    n = chain.shape[0]
    x = chain - jnp.mean(chain)
    nfft = 2 * n
    f = jnp.fft.rfft(x, nfft)
    acov = jnp.fft.irfft(f * jnp.conj(f), nfft)[:n].real / n
    rho = acov / acov[0]
    # Geyer: sum consecutive pairs Γ_k = ρ_{2k}+ρ_{2k+1}; truncate at first Γ<0.
    n_pairs = n // 2
    gamma = rho[0 : 2 * n_pairs : 2] + rho[1 : 2 * n_pairs : 2]
    positive = jnp.cumprod(gamma > 0.0)
    tau = -1.0 + 2.0 * jnp.sum(jnp.where(positive, gamma, 0.0))
    return n / jnp.maximum(tau, 1.0)


@partial(jax.jit, static_argnames=("chunk",))
def mmd2_rbf(
    x: jnp.ndarray, y: jnp.ndarray, lengthscale: float | jnp.ndarray, *, chunk: int = 512
) -> jnp.ndarray:
    """Biased MMD² with an RBF kernel (sanity-check metric alongside d₂)."""
    v = 2.0 * jnp.asarray(lengthscale) ** 2

    def mean_k(a, b):
        lse = log_mean_gaussian_cross(a, b, v, chunk=chunk)
        d = a.shape[-1]
        # undo the Gaussian normalizer so k(0)=1
        return jnp.exp(lse + 0.5 * d * (jnp.log(v) + _LOG2PI))

    return mean_k(x, x) + mean_k(y, y) - 2.0 * mean_k(x, y)
