"""§7/§8 experimental baselines: subpostAvg, subpostPool, consensus MC.

Each baseline has two faces: the raw array function (``subpost_average`` /
``pool`` / ``consensus_weighted`` — the historical API, re-exported by the
``repro.core.combine`` shim) and a registered adapter with the uniform
combiner signature so registry consumers can score them alongside the exact
combiners.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.combiners.api import (
    CombineResult,
    counts_or_full,
    ragged_gather,
    register,
    valid_masks,
)
from repro.core.gaussian import fit_moments


def subpost_average(
    samples: jnp.ndarray, *, counts: Optional[jnp.ndarray] = None
) -> jnp.ndarray:
    """"subpostAvg": θ_t = (1/M) Σ_m θ^m_t — one aligned draw per machine.

    With ragged counts, index t wraps modulo counts[m] so every machine always
    contributes (the baseline stays defined under stragglers).
    """
    counts = counts_or_full(samples, counts)
    return jnp.mean(ragged_gather(samples, counts), axis=0)


def consensus_weighted(
    samples: jnp.ndarray, *, counts: Optional[jnp.ndarray] = None
) -> jnp.ndarray:
    """Consensus Monte Carlo (Scott et al. 2013): precision-weighted averaging

        θ_t = (Σ_m Σ̂_m^{-1})^{-1} Σ_m Σ̂_m^{-1} θ^m_t.

    The paper (§7) views this as a relaxation of Algorithm 1; it is one of the
    experimental baselines.
    """
    M, T, d = samples.shape
    counts = counts_or_full(samples, counts)
    masks = valid_masks(samples, counts)
    moments = jax.vmap(lambda s, mk: fit_moments(s, mk))(samples, masks)
    precs = jax.vmap(lambda c: jnp.linalg.inv(c + 1e-10 * jnp.eye(d)))(moments.cov)
    total = jnp.sum(precs, axis=0)
    chol = jnp.linalg.cholesky(total)
    gathered = ragged_gather(samples, counts)  # (M, T, d)
    weighted = jnp.einsum("mij,mtj->ti", precs, gathered, precision=jax.lax.Precision.HIGHEST)
    return jax.scipy.linalg.cho_solve((chol, True), weighted.T).T


def pool(samples: jnp.ndarray, *, counts: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """"subpostPool": the union of all subposterior samples.

    Ragged counts: invalid rows are replaced by wrapping valid ones so the
    output stays a dense ``(M·T, d)`` array.
    """
    M, T, d = samples.shape
    counts = counts_or_full(samples, counts)
    return ragged_gather(samples, counts).reshape(M * T, d)


# ---------------------------------------------------------------------------
# registry adapters (uniform combiner signature; ``n_draws`` selects rows
# for baselines whose natural output length is fixed by T)
# ---------------------------------------------------------------------------


def _as_result(draws: jnp.ndarray, n_draws: int) -> CombineResult:
    """Resize subpostAvg/consensus output (naturally T rows) to ``n_draws``:
    even stride when shrinking, wrap when growing."""
    if n_draws <= draws.shape[0]:
        idx = (jnp.arange(n_draws) * draws.shape[0]) // n_draws
    else:
        idx = jnp.arange(n_draws) % draws.shape[0]
    return CombineResult(samples=draws[idx], acceptance_rate=jnp.ones(()))


@register("subpost_average", "subpostAvg")
def subpost_average_combiner(
    key: jax.Array,
    samples: jnp.ndarray,
    n_draws: int,
    *,
    counts: Optional[jnp.ndarray] = None,
    **_ignored,
) -> CombineResult:
    del key
    return _as_result(subpost_average(samples, counts=counts), n_draws)


@register("consensus")
def consensus_combiner(
    key: jax.Array,
    samples: jnp.ndarray,
    n_draws: int,
    *,
    counts: Optional[jnp.ndarray] = None,
    **_ignored,
) -> CombineResult:
    del key
    return _as_result(consensus_weighted(samples, counts=counts), n_draws)


@register("pool", "subpostPool")
def pool_combiner(
    key: jax.Array,
    samples: jnp.ndarray,
    n_draws: int,
    *,
    counts: Optional[jnp.ndarray] = None,
    **_ignored,
) -> CombineResult:
    """``n_draws`` is ignored: subpostPool *is* the full M·T union — returning
    a subsample would change what the baseline measures (and silently shift
    the benchmark numbers recorded before the registry rewire)."""
    del key, n_draws
    return CombineResult(
        samples=pool(samples, counts=counts), acceptance_rate=jnp.ones(())
    )
