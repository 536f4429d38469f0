"""Pallas kernel micro-benchmarks (interpret mode) + combination complexity.

Wall-times here are CPU-interpret numbers — meaningful as *correct-shape*
regression guards, not TPU latencies. The complexity check is the paper §4
claim: the incremental IMG sweep is O(dTM) — doubling M must ~double, not
~quadruple, the combine time.
"""

from __future__ import annotations

import time
from typing import List

import jax
import jax.numpy as jnp

from benchmarks.common import Row, block, timed
from repro.core.combiners import get_combiner
from repro.kernels import default_interpret
from repro.kernels.img_weights import img_log_weights, img_log_weights_ref
from repro.kernels.kde_density import (
    kde_log_density,
    kde_log_density_ref,
    machine_kde_log_density,
)
from repro.kernels.logreg_loglik import lane_dense, logreg_loglik_grad, logreg_loglik_grad_ref


def run(full: bool = False) -> List[Row]:
    rows: List[Row] = []
    key = jax.random.PRNGKey(0)

    # img_weights
    theta = jax.random.normal(key, (2048, 16, 64))
    t_k = timed(lambda: block(img_log_weights(theta, 0.5)))
    t_r = timed(lambda: block(img_log_weights_ref(theta, 0.5)))
    rows.append(Row("kernels", "img_weights_2048x16x64", "kernel_us", t_k * 1e6, "us", "interpret"))
    rows.append(Row("kernels", "img_weights_2048x16x64", "ref_us", t_r * 1e6, "us"))

    # logreg fused loglik+grad
    X = jax.random.normal(key, (50_000, 50))
    y = jnp.where(jax.random.uniform(jax.random.fold_in(key, 1), (50_000,)) < 0.5, 1.0, -1.0)
    beta = jax.random.normal(jax.random.fold_in(key, 2), (50,)) * 0.1
    ut = lane_dense(X, y)
    t_k = timed(lambda: block(logreg_loglik_grad(ut, beta)))
    t_r = timed(lambda: block(logreg_loglik_grad_ref(X, y, beta)))
    rows.append(Row("kernels", "logreg_50000x50", "kernel_us", t_k * 1e6, "us", "interpret"))
    rows.append(Row("kernels", "logreg_50000x50", "ref_us", t_r * 1e6, "us"))

    # kde streaming logsumexp
    q = jax.random.normal(key, (1024, 50))
    s = jax.random.normal(jax.random.fold_in(key, 3), (4096, 50))
    t_k = timed(lambda: block(kde_log_density(q, s, 0.5)))
    t_r = timed(lambda: block(kde_log_density_ref(q, s, 0.5)))
    rows.append(Row("kernels", "kde_1024x4096x50", "kernel_us", t_k * 1e6, "us", "interpret"))
    rows.append(Row("kernels", "kde_1024x4096x50", "ref_us", t_r * 1e6, "us"))

    # batched all-machines KDE scoring (PR 8 engine) — production routing:
    # Pallas kernel on real TPU, chunked jnp ref on CPU/interpret. The extra
    # records which path ran so interpret-mode CPU numbers are never read as
    # TPU kernel regressions.
    interp = default_interpret()
    route = f"interpret={interp} impl={'ref' if interp else 'kernel'}"
    Mk, Tk = 8, 4096
    mq = jax.random.normal(jax.random.fold_in(key, 4), (1024, 50))
    ms = jax.random.normal(jax.random.fold_in(key, 5), (Mk, Tk, 50))
    mh = jnp.full((Mk,), 0.5)
    t_full = timed(lambda: block(machine_kde_log_density(mq, ms, mh)))
    t_fused = timed(lambda: block(machine_kde_log_density(
        mq, ms, mh, reduce="product_mixture", mixture_weights="uniform")))
    rows.append(Row("kernels", "machine_kde_1024x8x4096", "op_us",
                    t_full * 1e6, "us", route))
    rows.append(Row("kernels", "machine_kde_1024x8x4096", "fused_us",
                    t_fused * 1e6, "us", route + " reduce=product_mixture"))

    # ---- §4 complexity: combine cost vs M (incremental = O(dTM)) ----------
    T, d = 400, 10
    times = {}
    nonparametric = get_combiner("nonparametric")
    for M in (4, 8, 16):
        samples = jax.random.normal(jax.random.fold_in(key, M), (M, T, d))
        fn = jax.jit(lambda k, s: nonparametric(k, s, T, rescale=True).samples)
        t = timed(lambda: block(fn(jax.random.PRNGKey(0), samples)), warmup=1, iters=3)
        times[M] = t
        rows.append(Row("complexity", f"M={M}", "img_combine_time", t, "s", f"T={T} d={d}"))
    growth_8_16 = times[16] / times[8]
    rows.append(Row("complexity", "M8->M16", "time_ratio", growth_8_16, "x",
                    "O(dTM) predicts ~2, O(dTM^2) predicts ~4"))
    return rows
