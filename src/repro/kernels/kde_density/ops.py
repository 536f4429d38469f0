"""jit'd wrappers for the streaming KDE log-density kernels."""

from __future__ import annotations

import functools
import math

from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.kde_density.kernel import machine_kde_log_density_kernel
from repro.kernels.kde_density.ref import (
    kde_log_density_ref,
    machine_kde_log_density_ref,
)


_LOG2PI = math.log(2.0 * math.pi)


def _round_up(n: int, k: int) -> int:
    return (n + k - 1) // k * k


@functools.partial(
    jax.jit,
    static_argnames=(
        "reduce", "mixture_weights", "block_q", "block_s", "chunk",
        "interpret", "impl", "min_kernel_n",
    ),
)
def machine_kde_log_density(
    queries: jnp.ndarray,  # (Q, d)
    samples: jnp.ndarray,  # (M, T, d)
    h: jnp.ndarray,  # (M,) or scalar per-machine bandwidth
    counts: Optional[jnp.ndarray] = None,  # (M,) int; None ⇒ all rows valid
    *,
    reduce: str = "none",
    mixture_weights: str = "counts",
    block_q: int = 256,
    block_s: int = 512,
    chunk: int = 256,
    interpret: bool | None = None,  # None -> repro.kernels.default_interpret()
    impl: str | None = None,  # None -> "kernel" on real TPU, "ref" elsewhere
    min_kernel_n: int = 64,
) -> Union[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Batched all-machines KDE scoring: one launch for every machine.

    ``reduce="none"`` returns the (M, Q) per-machine log densities;
    ``"product"`` / ``"mixture"`` / ``"product_mixture"`` return the fused
    (Q,) reductions without materializing (M, Q) on the kernel path. Dense
    (``counts is None``) and ragged chains share one code path: validity is a
    per-machine prefix applied inside the kernel / ref, so NaN garbage beyond
    ``counts[m]`` never reaches a max or exp.

    Routing: the Pallas kernel only pays off where it compiles to real TPU
    code — under interpret mode it is a correctness tool, not an execution
    engine, so CPU runs take the vectorized chunked jnp ref (which is also
    the path small problems take, below ``min_kernel_n``).
    """
    if interpret is None:
        interpret = default_interpret()
    if impl is None:
        impl = "ref" if interpret else "kernel"
    M, T, d = samples.shape
    Q = queries.shape[0]
    if impl == "ref" or Q < min_kernel_n or T < min_kernel_n:
        return machine_kde_log_density_ref(
            queries, samples, h, counts,
            reduce=reduce, mixture_weights=mixture_weights, chunk=chunk,
        )

    h_arr = jnp.broadcast_to(jnp.asarray(h, jnp.float32), (M,))
    counts_arr = (
        jnp.full((M,), T, jnp.int32) if counts is None else counts.astype(jnp.int32)
    )
    log_norm = jnp.log(jnp.maximum(counts_arr.astype(jnp.float32), 1.0)) + (
        0.5 * d * (2.0 * jnp.log(h_arr) + _LOG2PI)
    )
    if mixture_weights == "uniform":
        logw = jnp.full((M,), -math.log(M), jnp.float32)
    elif mixture_weights == "counts":
        cf = counts_arr.astype(jnp.float32)
        logw = jnp.log(cf) - jnp.log(jnp.sum(cf))
    else:
        raise ValueError(f"unknown mixture_weights={mixture_weights!r}")

    # ‖q − s‖² is shift-invariant, but the kernel expands it as
    # ‖q‖² + ‖s‖² − 2·q·s, which loses the digits the norms share. Posterior
    # draws sit far from the origin next to their spread (draws 0.03 apart at
    # distance ~10 came out 0.1 off in log density), so shift queries and
    # centers by the queries' mean first.
    center = jnp.mean(queries, axis=0)
    # queries ride the lane axis inside the kernel: tiles of 128
    block_q = min(block_q, _round_up(Q, 128))
    block_s = min(block_s, _round_up(T, 128))
    Qp, Tp = _round_up(Q, block_q), _round_up(T, block_s)
    qp_t = jnp.zeros((d, Qp), queries.dtype).at[:, :Q].set((queries - center).T)
    # T-padding needs no special handling: padded rows sit at index ≥ T ≥
    # counts[m] and fall out of the same in-kernel valid-prefix mask.
    sp = jnp.zeros((M, Tp, d), samples.dtype).at[:, :T].set(samples - center)
    out = machine_kde_log_density_kernel(
        qp_t, sp, 0.5 / (h_arr * h_arr), counts_arr, log_norm, logw,
        reduce=reduce, block_q=block_q, block_s=block_s, interpret=interpret,
    )
    if reduce == "none":
        return out[:, :Q]
    if reduce == "product_mixture":
        return out[0][:Q], out[1][:Q]
    return out[:Q]


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_s", "interpret", "min_kernel_n")
)
def kde_log_density(
    queries: jnp.ndarray,  # (nq, d)
    centers: jnp.ndarray,  # (ns, d)
    h: jnp.ndarray | float,
    *,
    block_q: int = 256,
    block_s: int = 512,
    interpret: bool | None = None,  # None -> repro.kernels.default_interpret()
    min_kernel_n: int = 64,
) -> jnp.ndarray:
    """Single-sample-set KDE log density: the one-machine case of the batched
    kernel (small problems take the pairwise ``jnp`` ref)."""
    nq, ns = queries.shape[0], centers.shape[0]
    if nq < min_kernel_n or ns < min_kernel_n:
        return kde_log_density_ref(queries, centers, h)
    return machine_kde_log_density(
        queries, centers[None], h,
        block_q=block_q, block_s=block_s, interpret=interpret, impl="kernel",
        min_kernel_n=min_kernel_n,
    )[0]
