"""Subposterior construction — paper Eq. 2.1.

Given a prior log-density, a per-datum log-likelihood, and a data shard, the
subposterior for machine m is

    p_m(θ) ∝ p(θ)^{1/M} · p(x^{n_m} | θ)

i.e. the shard's likelihood with an *underweighted* prior, so that the product
of all M subposteriors is proportional to the full-data posterior.

This module provides:

- :func:`partition_data`        deterministic arbitrary partition onto M shards
- :func:`make_subposterior_logpdf`   θ ↦ (1/M)·log p(θ) + Σ_{i∈shard} log p(x_i|θ)
- :func:`make_minibatch_logpdf`      the stochastic-gradient estimate used by SGLD
  at LM scale: (1/M)·log p(θ) + (N_m/B)·Σ_{i∈batch} log p(x_i|θ)
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp

PyTree = Any
LogDensityFn = Callable[[PyTree], jnp.ndarray]


def partition_data(
    data: PyTree,
    num_shards: int,
    shard_index: int | None = None,
    *,
    only: tuple[str, ...] | None = None,
    pad: bool = False,
) -> PyTree:
    """Partition leading axis of every per-datum leaf into equal shards.

    The paper allows *arbitrary* partitions for i.i.d. data; we use contiguous
    blocks (deterministic, reshard-friendly for elastic restarts).

    ``only``: names of dict keys that hold per-datum arrays; other leaves
    (global quantities like mixture weights) are broadcast unchanged to every
    shard. ``None`` = every leaf is per-datum.

    ``pad=False`` (default): ``N`` must be divisible by ``num_shards`` or a
    ``ValueError`` is raised. ``pad=True``: non-divisible ``N`` is padded up
    to ``M·ceil(N/M)`` rows by replicating the final datum, and the return
    value becomes ``(shards, counts)`` where ``counts (M,) int32`` is each
    shard's number of REAL rows — the same valid-prefix convention the
    combiners' ``counts=`` masking uses, so the vector can flow through the
    whole pipeline. Pass ``counts[m]`` as ``count=`` to
    :func:`make_subposterior_logpdf`, which subtracts the padded rows'
    (replicated-final-datum) likelihood exactly.

    Returns either shard ``shard_index`` or, if ``shard_index is None``, all
    shards stacked on a new leading axis ``(M, ceil(N/M), ...)``.
    """

    def _split(x):
        n = x.shape[0]
        if n % num_shards != 0:
            if not pad:
                raise ValueError(
                    f"leading dim {n} not divisible by M={num_shards} "
                    "(pass pad=True for edge-padded shards + counts)"
                )
            size = -(-n // num_shards)  # ceil(N/M)
            # edge padding: rows beyond N replicate the final datum (finite
            # for every model; make_subposterior_logpdf's `count` correction
            # removes their likelihood contribution exactly)
            idx = jnp.minimum(jnp.arange(num_shards * size), n - 1)
            x = x[idx]
        else:
            size = n // num_shards
        shards = x.reshape((num_shards, size) + x.shape[1:])
        return shards if shard_index is None else shards[shard_index]

    def _counts(n: int) -> jnp.ndarray:
        size = -(-n // num_shards)
        full = jnp.clip(n - jnp.arange(num_shards) * size, 0, size)
        counts = full.astype(jnp.int32)
        return counts if shard_index is None else counts[shard_index]

    if only is None:
        shards = jax.tree.map(_split, data)
        n_lead = jax.tree.leaves(data)[0].shape[0]
    else:
        if not isinstance(data, dict):
            raise TypeError("`only` requires dict data")
        shards = {k: (_split(v) if k in only else v) for k, v in data.items()}
        n_lead = data[only[0]].shape[0]
    if not pad:
        return shards
    return shards, _counts(n_lead)


def make_subposterior_logpdf(
    log_prior: LogDensityFn,
    log_lik: Callable[[PyTree, PyTree], jnp.ndarray],
    data_shard: PyTree,
    num_shards: int,
    *,
    count: jnp.ndarray | int | None = None,
    per_datum: tuple[str, ...] | None = None,
    form: PyTree | None = None,
) -> LogDensityFn:
    """Build the shard-m subposterior log-density (paper Eq. 2.1).

    ``log_lik(theta, data_shard)`` must return the *summed* log-likelihood of
    the shard. The prior is raised to 1/M in log space. With ``num_shards=1``
    this is the ordinary full-data posterior (used for groundtruth chains).

    ``count`` supports :func:`partition_data`'s ``pad=True`` shards: rows
    ``[count, S)`` are replicas of the shard's final row, so the exact masked
    log-likelihood is ``log_lik(shard) − (S − count)·log_lik(final row)``
    (log_lik is a per-datum sum by the model contract). ``count`` may be a
    traced scalar — the correction is O(1), vmap/shard_map friendly.
    ``per_datum`` names the dict keys holding per-datum arrays (same meaning
    as ``partition_data``'s ``only``; ``None`` = every leaf).
    ``form``, where given, is what ``log_lik`` reads for the whole shard (the
    model's ``shard_form`` of ``data_shard``); the correction's final row is
    still sliced from ``data_shard``'s raw rows.
    """

    inv_m = 1.0 / float(num_shards)
    whole = data_shard if form is None else form

    if count is None:
        def logpdf(theta: PyTree) -> jnp.ndarray:
            return inv_m * log_prior(theta) + log_lik(theta, whole)

        return logpdf

    if per_datum is None:
        last_row = jax.tree.map(lambda x: x[-1:], data_shard)
        shard_size = jax.tree.leaves(data_shard)[0].shape[0]
    else:
        last_row = {
            k: (v[-1:] if k in per_datum else v) for k, v in data_shard.items()
        }
        shard_size = data_shard[per_datum[0]].shape[0]
    n_pad = jnp.asarray(shard_size, jnp.float32) - jnp.asarray(count, jnp.float32)

    def logpdf(theta: PyTree) -> jnp.ndarray:
        full = log_lik(theta, whole)
        pad_ll = log_lik(theta, last_row)
        return inv_m * log_prior(theta) + full - n_pad * pad_ll

    return logpdf


def make_minibatch_logpdf(
    log_prior: LogDensityFn,
    log_lik: Callable[[PyTree, PyTree], jnp.ndarray],
    num_shards: int,
    shard_size: int,
) -> Callable[[PyTree, PyTree], jnp.ndarray]:
    """Unbiased minibatch estimator of the subposterior log-density.

    Used by SGLD/SGHMC at LM scale where a full-shard pass per step is not
    affordable: ``(1/M)·log p(θ) + (N_m/B)·log p(batch|θ)`` with B the batch's
    leading dim. The caller supplies a fresh batch per step.
    """

    inv_m = 1.0 / float(num_shards)

    def logpdf(theta: PyTree, batch: PyTree) -> jnp.ndarray:
        batch_size = jax.tree.leaves(batch)[0].shape[0]
        scale = shard_size / float(batch_size)
        return inv_m * log_prior(theta) + scale * log_lik(theta, batch)

    return logpdf


def mh_correction_ratio(
    log_prior: LogDensityFn,
    log_lik: Callable[[PyTree, PyTree], jnp.ndarray],
    data_shard: PyTree,
    num_shards: int,
) -> Callable[[PyTree, PyTree], jnp.ndarray]:
    """The paper §2 footnote form of the MH ratio on a subposterior:

    log [ p(θ*)^{1/M} p(x^{n_m}|θ*) ] − log [ p(θ)^{1/M} p(x^{n_m}|θ) ].

    Provided as a named helper so model code can be written once and reused
    for both full-posterior and subposterior sampling.
    """
    logpdf = make_subposterior_logpdf(log_prior, log_lik, data_shard, num_shards)

    def ratio(theta_new: PyTree, theta_old: PyTree) -> jnp.ndarray:
        return logpdf(theta_new) - logpdf(theta_old)

    return ratio
