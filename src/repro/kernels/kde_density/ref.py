"""Pure-jnp oracle: Gaussian-KDE log-density of queries under a sample set.

Used by the L2-distance metric (paper §8: d₂(p, p̂) between groundtruth and
combined samples) and by the semiparametric correction. For queries Q (nq, d)
and kernel centers S (ns, d) with bandwidth h:

    log p̂(q) = logsumexp_j [ −‖q − s_j‖² / (2h²) ] − log(ns) − (d/2)·log(2πh²)

The naive form materializes the (nq, ns) score matrix; the kernel streams it.
"""

from __future__ import annotations

import math

from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

# host-side, not jnp.log(...): module import must not run a JAX
# computation (jax.distributed.initialize refuses to start after one)
_LOG2PI = math.log(2.0 * math.pi)


def machine_kde_log_density_ref(
    queries: jnp.ndarray,  # (Q, d)
    samples: jnp.ndarray,  # (M, T, d)
    h: jnp.ndarray,  # (M,) or scalar bandwidth
    counts: Optional[jnp.ndarray] = None,  # (M,) int; None ⇒ all T rows valid
    *,
    reduce: str = "none",
    mixture_weights: str = "counts",
    chunk: int = 256,
) -> Union[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Chunked masked-logsumexp oracle for the batched all-machines KDE op.

    Scores every machine's Gaussian KDE at every query without materializing
    the (M, Q, T) tensor all at once: queries stream through ``lax.map`` in
    ``chunk``-row tiles, each tile scored against all machines by one einsum.
    Rows at index ≥ ``counts[m]`` are where-selected to −inf before the
    logsumexp, so NaN garbage in the invalid suffix is inert. ``reduce``
    mirrors the kernel's fused epilogues: ``"none"`` → (M, Q); ``"product"``
    → (Q,) Σ_m log p̂_m; ``"mixture"`` → (Q,) logsumexp_m(log w_m + log p̂_m)
    with w from ``counts`` or uniform; ``"product_mixture"`` → both.
    """
    M, T, d = samples.shape
    h = jnp.broadcast_to(jnp.asarray(h), (M,))
    counts = (
        jnp.full((M,), T, jnp.int32) if counts is None else counts.astype(jnp.int32)
    )

    mask = jnp.arange(T)[None, :] < counts[:, None]  # (M, T) bool
    csq = jnp.sum(samples**2, axis=-1)  # (M, T)
    Q = queries.shape[0]
    pad = (-Q) % chunk
    qp = jnp.pad(queries, ((0, pad), (0, 0))).reshape(-1, chunk, d)

    def block(qc):  # (chunk, d) → (M, chunk)
        sq = (
            jnp.sum(qc**2, axis=-1)[None, :, None]
            + csq[:, None, :]
            - 2.0 * jnp.einsum("qd,mtd->mqt", qc, samples, precision=jax.lax.Precision.HIGHEST)
        )
        logk = -0.5 * sq / (h[:, None, None] ** 2)
        logk = jnp.where(mask[:, None, :], logk, -jnp.inf)
        return jax.scipy.special.logsumexp(logk, axis=-1)

    out = jax.lax.map(block, qp)  # (n_chunks, M, chunk)
    lse = jnp.moveaxis(out, 0, 1).reshape(M, -1)[:, :Q]  # (M, Q)
    log_norm = (
        -jnp.log(jnp.maximum(counts.astype(queries.dtype), 1.0))
        - 0.5 * d * (2.0 * jnp.log(h) + _LOG2PI)
    )
    logp = lse + log_norm[:, None]

    if reduce == "none":
        return logp
    want_prod = reduce in ("product", "product_mixture")
    want_mix = reduce in ("mixture", "product_mixture")
    if not (want_prod or want_mix):
        raise ValueError(f"unknown reduce={reduce!r}")
    prod = jnp.sum(logp, axis=0) if want_prod else None
    mix = None
    if want_mix:
        if mixture_weights == "uniform":
            # subtract-after form: bitwise-identical to the historical
            # importance_pool reduction logsumexp(logp, 0) − log M
            mix = jax.scipy.special.logsumexp(logp, axis=0) - jnp.log(
                jnp.asarray(M, logp.dtype)
            )
        elif mixture_weights == "counts":
            cf = counts.astype(logp.dtype)
            logw = jnp.log(cf) - jnp.log(jnp.sum(cf))
            mix = jax.scipy.special.logsumexp(logp + logw[:, None], axis=0)
        else:
            raise ValueError(f"unknown mixture_weights={mixture_weights!r}")
    if want_prod and want_mix:
        return prod, mix
    return prod if want_prod else mix


def kde_log_density_ref(
    queries: jnp.ndarray,  # (nq, d)
    centers: jnp.ndarray,  # (ns, d)
    h: jnp.ndarray | float,
) -> jnp.ndarray:
    q = queries.astype(jnp.float32)
    s = centers.astype(jnp.float32)
    h = jnp.asarray(h, jnp.float32)
    d = q.shape[-1]
    sq = jnp.sum((q[:, None, :] - s[None, :, :]) ** 2, axis=-1)  # (nq, ns)
    lse = jax.scipy.special.logsumexp(-0.5 * sq / (h * h), axis=1)
    # the batched op's normalizer expression: log is approximate on a TPU,
    # and d/2 multiplies the difference between two ways of writing it
    return lse - jnp.log(s.shape[0]) - 0.5 * d * (2.0 * jnp.log(h) + _LOG2PI)
