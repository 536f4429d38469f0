"""Batched all-machines KDE scoring op: kernel-vs-ref parity + masking laws.

Covers the PR-8 contracts:
- the Pallas kernel (interpret=True) matches the chunked jnp ref on dense and
  ragged inputs;
- the dense path matches the historical per-machine loop over the
  single-machine ``kde_log_density`` kernel;
- the ragged ref is bitwise-identical to the pre-batching
  ``machine_kde_logpdfs`` masked-logsumexp implementation;
- NaN garbage in rows beyond ``counts[m]`` is provably inert;
- fused ``product`` / ``mixture`` epilogues equal the explicit reductions of
  the (M, Q) matrix;
- ``masked_silverman``'s bandwidth floor keeps constant chains finite.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.combiners.density import machine_kde_logpdfs, masked_silverman
from repro.kernels.kde_density import (
    kde_log_density,
    machine_kde_log_density,
    machine_kde_log_density_ref,
)


def _case(seed, M, T, d, Q, ragged):
    k = jax.random.PRNGKey(seed)
    ks = jax.random.split(k, 4)
    samples = jax.random.normal(ks[0], (M, T, d), jnp.float32)
    queries = jax.random.normal(ks[1], (Q, d), jnp.float32)
    h = jnp.abs(jax.random.normal(ks[2], (M,))) * 0.4 + 0.2
    if ragged:
        counts = jax.random.randint(ks[3], (M,), 1, T + 1).astype(jnp.int32)
        counts = counts.at[0].set(T)  # keep one dense machine in the mix
    else:
        counts = None
    return queries, samples, h, counts


def _allclose_lp(got, want, **kw):
    """allclose over log densities where both −inf (empty machines) agree."""
    got, want = np.asarray(got), np.asarray(want)
    inf = np.isneginf(got) & np.isneginf(want)
    assert not np.any(np.isnan(got))
    np.testing.assert_allclose(np.where(inf, 0.0, got), np.where(inf, 0.0, want), **kw)


@pytest.mark.parametrize("M,T,d,Q", [(5, 700, 7, 300), (3, 512, 50, 256), (8, 130, 2, 65), (2, 64, 1, 64)])
@pytest.mark.parametrize("ragged", [False, True])
def test_kernel_matches_ref(M, T, d, Q, ragged):
    queries, samples, h, counts = _case(M * T + Q, M, T, d, Q, ragged)
    got = machine_kde_log_density(
        queries, samples, h, counts, impl="kernel", interpret=True
    )
    want = machine_kde_log_density_ref(queries, samples, h, counts)
    assert got.shape == (M, Q)
    _allclose_lp(got, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("d", [20, 50])
def test_kernel_is_accurate_on_draws_far_from_the_origin(d):
    """Posterior draws: spread 0.03 around a point at distance 10. Expanding
    ‖q − s‖² in f32 at such norms puts log densities ~0.07 off (the ref,
    which does not shift, is); the kernel path shifts by the query mean and
    stays within 1e-4 of a float64 evaluation of direct differences."""
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    mu = jnp.full((d,), 10.0 / math.sqrt(d))
    samples = mu + 0.03 * jax.random.normal(ks[0], (3, 256, d))
    queries = mu + 0.03 * jax.random.normal(ks[1], (64, d))
    h = jnp.full((3,), 0.02)
    got = machine_kde_log_density(queries, samples, h, impl="kernel", interpret=True)

    q, s = np.asarray(queries, np.float64), np.asarray(samples, np.float64)
    logk = -0.5 * ((q[None, :, None] - s[:, None]) ** 2).sum(-1) / 0.02**2
    top = logk.max(-1)
    want = (top + np.log(np.exp(logk - top[..., None]).sum(-1))
            - math.log(256) - 0.5 * d * math.log(2 * math.pi * 0.02**2))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4)


@pytest.mark.parametrize("impl", ["ref", "kernel"])
def test_dense_matches_per_machine_loop(impl):
    """The batched op ≡ the historical M-launch loop on dense chains."""
    queries, samples, h, _ = _case(11, 6, 400, 10, 200, ragged=False)
    got = machine_kde_log_density(
        queries, samples, h, None, impl=impl, interpret=True
    )
    want = jnp.stack(
        [kde_log_density(queries, samples[m], h[m]) for m in range(6)]
    )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_ragged_ref_bitwise_matches_historical_masked_path():
    """ref ≡ the pre-batching chunked masked-logsumexp, bit for bit."""
    queries, samples, h, counts = _case(23, 5, 300, 8, 270, ragged=True)

    # the exact pre-PR8 machine_kde_logpdfs ragged implementation
    M, T, d = samples.shape
    chunk = 256
    mask = jnp.arange(T)[None, :] < counts[:, None]
    csq = jnp.sum(samples**2, axis=-1)
    Q = queries.shape[0]
    pad = (-Q) % chunk
    qp = jnp.pad(queries, ((0, pad), (0, 0))).reshape(-1, chunk, d)

    def block(qc):
        sq = (
            jnp.sum(qc**2, axis=-1)[None, :, None]
            + csq[:, None, :]
            - 2.0 * jnp.einsum("qd,mtd->mqt", qc, samples)
        )
        logk = -0.5 * sq / (h[:, None, None] ** 2)
        logk = jnp.where(mask[:, None, :], logk, -jnp.inf)
        return jax.scipy.special.logsumexp(logk, axis=-1)

    out = jax.lax.map(block, qp)
    lse = jnp.moveaxis(out, 0, 1).reshape(M, -1)[:, :Q]
    log_norm = (
        -jnp.log(jnp.maximum(counts.astype(queries.dtype), 1.0))
        - 0.5 * d * (2.0 * jnp.log(h) + math.log(2.0 * math.pi))
    )
    want = lse + log_norm[:, None]

    got = machine_kde_log_density_ref(queries, samples, h, counts)
    assert bool(jnp.all(got == want))
    # and the density.py helper routes ragged calls through the same ref. The
    # helper runs it under jit, and XLA promises no bitwise agreement between
    # a jitted program and the same ops run eagerly (fusion may reassociate),
    # so the helper is held bitwise to the jitted ref instead.
    via_helper = machine_kde_logpdfs(queries, samples, counts, h)
    jitted = jax.jit(machine_kde_log_density_ref)(queries, samples, h, counts)
    assert bool(jnp.all(via_helper == jitted))


@pytest.mark.parametrize("impl", ["ref", "kernel"])
def test_nan_garbage_beyond_counts_is_inert(impl):
    """Scores with NaN-poisoned invalid rows ≡ scores with clean rows."""
    queries, samples, h, counts = _case(37, 5, 400, 6, 200, ragged=True)
    counts = counts.at[2].set(0)  # empty machine: all rows garbage
    tidx = jnp.arange(samples.shape[1])[None, :, None]
    poisoned = jnp.where(tidx < counts[:, None, None], samples, jnp.nan)

    clean = machine_kde_log_density(
        queries, samples, h, counts, impl=impl, interpret=True
    )
    dirty = machine_kde_log_density(
        queries, poisoned, h, counts, impl=impl, interpret=True
    )
    assert not bool(jnp.any(jnp.isnan(dirty)))
    inf = jnp.isneginf(clean) & jnp.isneginf(dirty)
    assert bool(jnp.all(inf | (clean == dirty)))
    # the empty machine scores −inf everywhere (its KDE has no support)
    assert bool(jnp.all(jnp.isneginf(dirty[2])))


@pytest.mark.parametrize("impl", ["ref", "kernel"])
@pytest.mark.parametrize("weights", ["uniform", "counts"])
def test_fused_reductions_match_explicit(impl, weights):
    queries, samples, h, counts = _case(53, 6, 500, 5, 300, ragged=True)
    full = machine_kde_log_density(
        queries, samples, h, counts, impl=impl, interpret=True
    )
    prod, mix = machine_kde_log_density(
        queries, samples, h, counts,
        reduce="product_mixture", mixture_weights=weights,
        impl=impl, interpret=True,
    )
    prod_only = machine_kde_log_density(
        queries, samples, h, counts, reduce="product", impl=impl, interpret=True
    )
    mix_only = machine_kde_log_density(
        queries, samples, h, counts,
        reduce="mixture", mixture_weights=weights, impl=impl, interpret=True,
    )
    M = samples.shape[0]
    want_prod = jnp.sum(full, axis=0)
    if weights == "uniform":
        want_mix = jax.scipy.special.logsumexp(full, axis=0) - jnp.log(float(M))
    else:
        cf = counts.astype(full.dtype)
        logw = jnp.log(cf) - jnp.log(jnp.sum(cf))
        want_mix = jax.scipy.special.logsumexp(full + logw[:, None], axis=0)
    _allclose_lp(prod, want_prod, rtol=1e-5, atol=1e-4)
    _allclose_lp(prod_only, want_prod, rtol=1e-5, atol=1e-4)
    _allclose_lp(mix, want_mix, rtol=1e-5, atol=1e-4)
    _allclose_lp(mix_only, want_mix, rtol=1e-5, atol=1e-4)


def test_fused_uniform_mixture_bitwise_matches_importance_pool_form():
    """ref ``mixture_weights="uniform"`` ≡ logsumexp(logp, 0) − log M exactly
    (the historical importance_pool proposal reduction)."""
    queries, samples, h, counts = _case(71, 4, 300, 3, 200, ragged=True)
    full = machine_kde_log_density_ref(queries, samples, h, counts)
    mix = machine_kde_log_density_ref(
        queries, samples, h, counts, reduce="mixture", mixture_weights="uniform"
    )
    want = jax.scipy.special.logsumexp(full, axis=0) - jnp.log(
        jnp.asarray(4, full.dtype)
    )
    assert bool(jnp.all(mix == want))


def test_vmap_over_pairs():
    """The tree-reduction usage: vmap the helper over stacked machine pairs."""
    queries, samples, h, counts = _case(89, 6, 200, 4, 100, ragged=True)
    pairs = samples.reshape(3, 2, 200, 4)
    pair_counts = counts.reshape(3, 2)
    pair_h = h.reshape(3, 2)
    got = jax.vmap(
        lambda s, c, hh: machine_kde_logpdfs(queries, s, c, hh)
    )(pairs, pair_counts, pair_h)
    for p in range(3):
        want = machine_kde_logpdfs(queries, pairs[p], pair_counts[p], pair_h[p])
        _allclose_lp(got[p], want, rtol=1e-6, atol=1e-6)


def test_masked_silverman_floor_keeps_constant_chain_finite():
    """A constant chain has σ=0; the 1e-8 bandwidth floor must keep its own
    scores finite instead of NaN-poisoning the pooled logits."""
    M, T, d = 3, 50, 4
    samples = jax.random.normal(jax.random.PRNGKey(0), (M, T, d), jnp.float32)
    samples = samples.at[1].set(1.5)  # machine 1: every draw identical
    counts = jnp.full((M,), T, jnp.int32)
    h = masked_silverman(samples, counts)
    assert bool(jnp.all(h >= 1e-8))
    # scoring the constant chain's own location stays finite for machine 1
    q = jnp.concatenate([jnp.full((1, d), 1.5), samples[0, :4]])
    logp = machine_kde_log_density(q, samples, h, counts)
    assert bool(jnp.isfinite(logp[1, 0]))
    assert not bool(jnp.any(jnp.isnan(logp)))
    # single-draw chains hit the same floor path
    h1 = masked_silverman(samples, jnp.array([1, 1, 1], jnp.int32))
    assert bool(jnp.all(h1 >= 1e-8)) and not bool(jnp.any(jnp.isnan(h1)))
