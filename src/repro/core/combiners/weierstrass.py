"""Weierstrass refinement sampler — exact Gibbs over latent per-machine draws.

Wang & Dunson's Weierstrass transform view of the density product: replace
each subposterior p_m with its Gaussian-smoothed version
``∫ N(θ | θ_m, h²I) p_m(θ_m) dθ_m`` and sample the *extended* model over
(θ, θ¹, …, θᴹ) by Gibbs. With the empirical (sample-cloud) approximation of
each p_m, both conditionals are exact and closed-form:

1. refinement step — for each machine m, the latent θᵐ is one of chain m's
   stored draws, selected with probability ∝ N(θ | θᵐ_t, h²I) over the valid
   prefix (a softmax of negative squared distances — the KDE responsibilities
   of θ under machine m's cloud);
2. pooling step — θ | θ¹..θᴹ ~ N(θ̄, h²/M · I), the product of the M
   Gaussian kernels around the selected latents.

No accept/reject anywhere (acceptance ≡ 1): unlike the IMG combiners, every
sweep refreshes *all* M latent indices from their full conditionals, so
mixing does not degrade with M. The price is O(M·T·d) per sweep (a dense
distance matvec) versus IMG's O(M·d) incremental recursion.

As h → 0 the smoothed product converges to the product of subposterior KDEs
— the same asymptotically exact target as Algorithm 1 — so the combiner
reuses the shared shrinking-``bandwidth`` anneal schedules (``rescale=True``
starts h at the pooled sample scale).

Initialization: the default start is a uniform pooled draw — the analog of
Algorithm 1's uniform index init, whose wide early-anneal transient is part
of the emitted trajectory by convention. ``init_pool > 0`` switches to a
density-guided start: it scores a strided subsample of the pooled cloud
under Σ_m log p̂_m via the batched ``machine_kde_log_density`` op (fused
product epilogue — one launch, no (M, pool) matrix on the kernel path) and
draws each chain's θ₀ from the softmax of those scores — chains start in
the product's high-density region, cutting the transient (useful when the
combined draws feed a downstream consumer rather than a KDE metric). The
final latent states are scored by the Pallas ``img_weights`` kernel and
reported in ``extras["final_log_weight"]`` — directly comparable to the IMG
chain's mixture weight w_t at the same bandwidth.

``n_chains=B`` (default 8) runs an ensemble of independent Gibbs chains
under ``vmap`` with the same shared global anneal index as the batched IMG
engine: chain b's sweep i anneals at h(i·B + b + 1), and draws interleave
to one (n_draws, d) output. The ensemble is this combiner's natural
parallelism *and* robustness knob — independent diffuse starts cover a
thin or multi-well product overlap region the way ``rpt``'s ``n_trees``
covers partition noise — and is deliberately distinct from the IMG
engine's ``n_batch`` (the CLI's ``--img-batch`` tunes IMG index chains,
not this ensemble).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.combiners.api import (
    CombineResult,
    Schedule,
    counts_or_full,
    ragged_gather,
    register,
    resolve_schedule,
)
from repro.core.combiners.density import machine_kde_scores, masked_silverman


@register("weierstrass", "weierstrass_refine")
def weierstrass(
    key: jax.Array,
    samples: jnp.ndarray,
    n_draws: int,
    *,
    counts: Optional[jnp.ndarray] = None,
    schedule: Optional[Schedule] = None,
    rescale: bool = False,
    n_chains: int = 8,
    init_pool: int = 0,
    **_ignored,
) -> CombineResult:
    """Gibbs refinement sampling from the Weierstrass-smoothed density product.

    ``n_chains``: ensemble size (independent Gibbs chains, interleaved
    draws). ``init_pool``: 0 (default) starts each chain at a uniform pooled
    draw (Algorithm 1's diffuse-init convention); > 0 enables the
    density-guided start over a strided pooled subsample of that size.
    """
    M, T, d = samples.shape
    dtype = samples.dtype
    counts_arr = counts_or_full(samples, counts)
    schedule = resolve_schedule(samples, schedule, rescale)
    n_batch = max(1, min(int(n_chains), int(n_draws)))
    n_sweeps = -(-n_draws // n_batch)  # ceil

    k_init, k_run = jax.random.split(key)
    pooled = ragged_gather(samples, counts_arr).reshape(M * T, d)
    if init_pool and init_pool > 0:
        h0 = masked_silverman(samples, counts_arr)  # (M,)
        stride = max(1, (M * T) // min(int(init_pool), M * T))
        cand = pooled[::stride]
        # Σ_m log p̂_m over the candidate pool — one fused batched-KDE launch,
        # product epilogue (no (M, pool) matrix).
        score = machine_kde_scores(
            cand, samples, counts if counts is None else counts_arr, h0,
            reduce="product",
        )
        idx0 = jax.random.categorical(k_init, score, shape=(n_batch,))
        theta0 = cand[idx0]  # (B, d)
    else:
        idx0 = jax.random.randint(k_init, (n_batch,), 0, M * T)
        theta0 = pooled[idx0]

    mask = jnp.arange(T)[None, :] < counts_arr[:, None]  # (M, T)
    csq = jnp.where(mask, jnp.sum(samples**2, axis=-1), 0.0)  # (M, T)
    offsets = jnp.arange(1, n_batch + 1, dtype=jnp.float32)  # shared global anneal
    inv_sqrt_m = 1.0 / jnp.sqrt(jnp.asarray(M, dtype))

    def sweep(carry, i):
        theta, sel, k = carry  # (B, d), (B, M, d), key
        h = schedule(offsets + i * n_batch).astype(dtype)  # (B,)
        k, k_ref, k_pool = jax.random.split(k, 3)
        # refinement: categorical over each machine's valid prefix with
        # logits −‖θ − θᵐ_t‖²/(2h²), drawn via Gumbel-max in one shot.
        cross = jnp.einsum("mtd,bd->bmt", samples, theta, precision=jax.lax.Precision.HIGHEST)
        qsq = jnp.sum(theta**2, axis=-1)  # (B,)
        sq = csq[None, :, :] - 2.0 * cross + qsq[:, None, None]
        logits = -0.5 * sq / (h[:, None, None] ** 2)
        logits = jnp.where(mask[None, :, :], logits, -jnp.inf)
        gumbel = jax.random.gumbel(k_ref, logits.shape, logits.dtype)
        t_sel = jnp.argmax(logits + gumbel, axis=-1)  # (B, M)
        sel = samples[jnp.arange(M)[None, :], t_sel]  # (B, M, d)
        # pooling: θ ~ N(θ̄, h²/M I) — the product of the M kernels.
        eps = jax.random.normal(k_pool, (theta.shape[0], d), dtype)
        theta = jnp.mean(sel, axis=1) + eps * (h[:, None] * inv_sqrt_m)
        return (theta, sel, k), theta

    init = (theta0, jnp.zeros((n_batch, M, d), dtype), k_run)
    (theta_f, sel_f, _), draws = jax.lax.scan(sweep, init, jnp.arange(n_sweeps))

    # scan emits (n_sweeps, B, d): flattening interleaves chains so row
    # i·B + b carries anneal index i·B + b + 1 — the serial ordering. Drop
    # the earliest (least annealed) ceil-surplus rows.
    draws = draws.reshape(n_sweeps * n_batch, d)[-n_draws:]

    from repro.kernels.img_weights import img_log_weights

    h_final = schedule(jnp.asarray(n_sweeps * n_batch, jnp.float32))
    final_lw = img_log_weights(sel_f, h_final.astype(jnp.float32))  # (B,)
    return CombineResult(
        samples=draws,
        acceptance_rate=jnp.ones(()),  # exact Gibbs: every sweep accepted
        moments=None,
        extras={
            "n_chains": jnp.asarray(n_batch),
            "n_sweeps_per_chain": jnp.asarray(n_sweeps),
            "h_final": h_final,
            "final_log_weight": final_lw,
        },
    )
