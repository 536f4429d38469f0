"""Online parametric combiner (paper §4: combine as samples stream in).

The Welford/product machinery keeps O(d²) state per machine and needs O(1)
work per sample, so the parametric product estimate is available at *any*
point of the stream — no gathered ``(M, T, d)`` stack required. It is
registered as the ``online`` combiner with both faces:

- batch: ``online(key, samples, n_draws, counts=...)`` folds the whole
  stack through one chunk update and samples the product — so
  ``--combiner online`` works from ``mcmc_run`` / ``bench_combine`` even
  outside streaming mode;
- streaming: the registry's :class:`~repro.core.combiners.api.StreamingCombiner`
  slot, whose state *is* :class:`OnlineMoments` — the one built-in combiner
  that never buffers draws.

The scan face (fused streaming hot path) folds chunks through the Pallas
``online_update`` kernel via :func:`online_update_chunk_kernel`. The
merge-rounding tolerance contract lives next to that kernel, in
:mod:`repro.kernels.online_update.ops` — in short: Welford merges associate
differently across chunkings and evaluation orders, so streamed/fused
``online`` runs agree with the batch face to f32 last-ulp per fold, never
bitwise; the exact-bitwise streaming guarantee belongs to the buffered
combiners (see ``api.buffered_streaming``).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.combiners.api import (
    CombineResult,
    ScanStreamingFace,
    StreamingCombiner,
    counts_or_full,
    register,
    register_scan_face,
)
from repro.core.gaussian import GaussianMoments, product_moments, sample_gaussian


class OnlineMoments(NamedTuple):
    """Welford running moments per subposterior — O(d²) state, O(1) per sample."""

    count: jnp.ndarray  # (M,)
    mean: jnp.ndarray  # (M, d)
    m2: jnp.ndarray  # (M, d, d) sum of outer products of residuals


def online_init(M: int, d: int, dtype=jnp.float32) -> OnlineMoments:
    return OnlineMoments(
        count=jnp.zeros((M,), dtype),
        mean=jnp.zeros((M, d), dtype),
        m2=jnp.zeros((M, d, d), dtype),
    )


def online_update(state: OnlineMoments, m: jnp.ndarray, theta: jnp.ndarray) -> OnlineMoments:
    """Fold one new sample ``theta`` (d,) from machine ``m`` into the moments."""
    n = state.count[m] + 1.0
    delta = theta - state.mean[m]
    mean_m = state.mean[m] + delta / n
    m2_m = state.m2[m] + jnp.outer(delta, theta - mean_m)
    return OnlineMoments(
        count=state.count.at[m].set(n),
        mean=state.mean.at[m].set(mean_m),
        m2=state.m2.at[m].set(m2_m),
    )


def online_update_chunk(
    state: OnlineMoments,
    chunk: jnp.ndarray,
    chunk_counts: Optional[jnp.ndarray] = None,
) -> OnlineMoments:
    """Fold a dense ``(M, C, d)`` chunk into the moments (Chan's parallel
    Welford merge, vectorized over machines).

    ``chunk_counts (M,)`` marks each machine's valid prefix within the chunk
    (None ⇒ all C rows). Invalid rows may hold arbitrary garbage — they are
    excluded with ``where``, never mask-multiplied (0·NaN would leak).
    """
    from repro.kernels.online_update.ref import online_moments_update_ref

    count, mean, m2 = online_moments_update_ref(
        state.count, state.mean, state.m2, chunk, chunk_counts
    )
    return OnlineMoments(count=count, mean=mean, m2=m2)


def online_update_chunk_kernel(
    state: OnlineMoments,
    chunk: jnp.ndarray,
    chunk_counts: Optional[jnp.ndarray] = None,
) -> OnlineMoments:
    """Pallas-backed chunk fold: same merge as :func:`online_update_chunk`,
    computed by the fused ``online_update`` kernel
    (:func:`repro.kernels.online_update.online_moments_update` — batch
    moments + Chan merge in one VMEM-resident pass per machine). Agreement
    with the jnp path is f32 last-ulp per fold; see the tolerance note in
    :mod:`repro.kernels.online_update.ops`. jit-safe — this is the scan
    face's update on the fused streaming hot path.
    """
    from repro.kernels.online_update import online_moments_update

    count, mean, m2 = online_moments_update(
        state.count, state.mean, state.m2, chunk, chunk_counts
    )
    return OnlineMoments(count=count, mean=mean, m2=m2)


def online_product(state: OnlineMoments, *, jitter: float = 1e-8) -> GaussianMoments:
    """Current parametric product estimate from streaming moments."""
    d = state.mean.shape[-1]
    denom = jnp.maximum(state.count - 1.0, 1.0)[:, None, None]
    covs = state.m2 / denom + jitter * jnp.eye(d)
    return product_moments(state.mean, covs)


def _finalize(
    key: jax.Array,
    state: OnlineMoments,
    n_draws: int,
    *,
    jitter: float = 1e-8,
    **_ignored,
) -> CombineResult:
    prod = online_product(state, jitter=jitter)
    draws = sample_gaussian(key, prod, n_draws)
    return CombineResult(samples=draws, acceptance_rate=jnp.ones(()), moments=prod)


# estimate IS finalize: sampling the moment product is already O(d²) — the
# cheapest mid-stream snapshot any combiner has. Declaring it (rather than
# leaving None-means-cheap implicit) lets trajectory consumers and the
# serving layer treat `estimate is None` uniformly as "cannot refresh".
ONLINE_STREAMING = StreamingCombiner(
    init=online_init,
    update=online_update_chunk,
    finalize=_finalize,
    estimate=_finalize,
)


@register("online", "online_parametric", streaming=ONLINE_STREAMING)
def online(
    key: jax.Array,
    samples: jnp.ndarray,
    n_draws: int,
    *,
    counts: Optional[jnp.ndarray] = None,
    jitter: float = 1e-8,
    **_ignored,
) -> CombineResult:
    """Batch face of the streaming moments: one whole-stack chunk update."""
    counts = counts_or_full(samples, counts)
    M, _, d = samples.shape
    state = online_update_chunk(online_init(M, d, samples.dtype), samples, counts)
    return _finalize(key, state, n_draws, jitter=jitter)


def _online_scan_estimate(
    key, state: OnlineMoments, n_draws: int, *, jitter: float = 1e-8, **_ignored
) -> jnp.ndarray:
    """In-scan trajectory draws: the same moment-product sample as the host
    ``estimate``, as raw draws — traced into the fused combine-fold step."""
    return sample_gaussian(key, online_product(state, jitter=jitter), n_draws)


# Scan face (fused streaming): the host state already IS the scan state —
# OnlineMoments pass through ``to_state`` untouched, and chunk folds run the
# Pallas kernel. The in-scan ``estimate`` mirrors the host one, so fused and
# subscriber streams emit rows at the same boundaries (agreeing to Welford
# merge-rounding — the kernel-vs-jnp fold tolerance documented above).
ONLINE_SCAN = register_scan_face(
    "online",
    ScanStreamingFace(
        init=online_init,
        update=online_update_chunk_kernel,
        to_state=lambda scan_state, theta, counts: scan_state,
        estimate=_online_scan_estimate,
    ),
)
