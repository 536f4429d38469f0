"""Bayesian linear regression with known noise: a model file for the tests.

y = Xβ + ε with ε ~ N(0, 1) and β ~ N(0, τ² I), as the program's
``linear_gaussian`` model states it. τ is the configuration's
``prior_sigma``, 3 (the program's default) where it states none:
``bayes_model`` builds the sampled model with it. Every subposterior, with
the prior to the power 1/M, and the full posterior are Gaussian, so the
reference is exact and in closed form (float64, on the host). It keeps to
the contract of ``chipbench.harness`` and shares no code with the
logistic-regression model file or ``chipbench.check``: its numbers are its
own.

- ``shard_z``: the worst |draw mean − exact mean| / exact sd over the
  shards and coordinates of one job;
- ``shard_log_sd``: the worst |log(draw sd / exact sd)| there;
- ``product_z``, ``product_log_sd``: the same two for the combined draws
  against the full posterior;
- ``shard_z_mean``: ``shard_z`` of the draw means averaged over the
  window's jobs, which sheds the Monte Carlo error of one job.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

TAU, NOISE = 3.0, 1.0

NUMBERS = {
    "shard_z": {"layer": "sampling", "scope": "job"},
    "shard_log_sd": {"layer": "sampling", "scope": "job"},
    "product_z": {"layer": "combine", "scope": "job"},
    "product_log_sd": {"layer": "combine", "scope": "job"},
    "shard_z_mean": {"layer": "sampling", "scope": "window"},
}


def _tau(cfg: Dict) -> float:
    return float(cfg.get("prior_sigma", TAU))


def bayes_model(cfg: Dict):
    """The program's linear-Gaussian model under the configuration's prior."""
    from repro.models.bayes import get_model, linear_gaussian

    tau = _tau(cfg)
    return dataclasses.replace(
        get_model("linear_gaussian"), name=f"linear.prior_sigma={tau!r}",
        log_prior=functools.partial(linear_gaussian.log_prior, tau=tau))


def make_data(key: jax.Array, cfg: Dict) -> Dict[str, jax.Array]:
    n, d = int(cfg["N"]), int(cfg["d"])

    @jax.jit
    def build(key):
        k_beta, k_x, k_eps = jax.random.split(key, 3)
        beta = jax.random.normal(k_beta, (d,), jnp.float32)
        x = jax.random.normal(k_x, (n, d), jnp.float32)
        return {"x": x, "y": x @ beta + NOISE * jax.random.normal(k_eps, (n,), jnp.float32)}

    return build(key)


def job_flops(cfg: Dict) -> float:
    """X·θ and Xᵀ·r per transition, 2·N·d FLOPs each."""
    steps = int(cfg["warmup"]) + int(cfg["burn_in"]) + int(cfg["T"])
    return 4.0 * float(cfg["N"]) * float(cfg["d"]) * steps


def handoff(sample) -> jax.Array:
    return sample.theta


def _gaussian(x, y, prior_prec):
    prec = prior_prec * np.eye(x.shape[1]) + x.T @ x / NOISE**2
    cov = np.linalg.inv(prec)
    return cov @ (x.T @ y) / NOISE**2, np.sqrt(np.diag(cov))


def reference(data: Dict[str, jax.Array], cfg: Dict) -> Dict[str, np.ndarray]:
    """Exact means and sds of the M contiguous shards' subposteriors and of
    the full posterior (the configuration's N divides by its M)."""
    x = np.asarray(data["x"], np.float64)
    y = np.asarray(data["y"], np.float64)
    m, tau = int(cfg["M"]), _tau(cfg)
    subs = [_gaussian(xs, ys, 1.0 / (m * tau**2))
            for xs, ys in zip(np.split(x, m), np.split(y, m))]
    full_mean, full_sd = _gaussian(x, y, 1.0 / tau**2)
    return {"sub_mean": np.stack([s[0] for s in subs]),
            "sub_sd": np.stack([s[1] for s in subs]),
            "full_mean": full_mean, "full_sd": full_sd}


def summarize(output, cfg: Dict) -> Dict[str, np.ndarray]:
    (combined,) = output.combine.values()
    sub = np.asarray(output.sample.theta, np.float64)
    comb = np.asarray(combined.samples, np.float64)
    return {"sub_mean": sub.mean(-2), "sub_sd": sub.std(-2, ddof=1),
            "comb_mean": comb.mean(-2), "comb_sd": comb.std(-2, ddof=1)}


def _worst(values) -> float:
    return float(np.max(np.abs(values)))


def readings(summaries: List[Dict[str, np.ndarray]], ref, cfg: Dict):
    per_job = [{
        "shard_z": _worst((s["sub_mean"] - ref["sub_mean"]) / ref["sub_sd"]),
        "shard_log_sd": _worst(np.log(s["sub_sd"] / ref["sub_sd"])),
        "product_z": _worst((s["comb_mean"] - ref["full_mean"]) / ref["full_sd"]),
        "product_log_sd": _worst(np.log(s["comb_sd"] / ref["full_sd"])),
    } for s in summaries]
    window = {k: max(r[k] for r in per_job) for k in per_job[0]}
    mean = np.mean([s["sub_mean"] for s in summaries], axis=0)
    window["shard_z_mean"] = _worst((mean - ref["sub_mean"]) / ref["sub_sd"])
    return per_job, window
