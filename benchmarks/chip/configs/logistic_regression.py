"""Bayesian logistic regression (arXiv:1311.4780 §8.1): data, work and reference.

Shared by every configuration file here whose ``model_file`` names it. It holds
what the benchmark needs about the model, in the contract of
``chipbench.harness``, and takes nothing from the program but the results
it checks:

- ``make_data``: the data set from a key, in one jitted call on the device.
  The two designs are the paper's synthetic set (§8.1.1: X, β ~ N(0, 1),
  y ~ Bernoulli(σ(Xβ)), no intercept) and the covertype-shaped stand-in
  (§8.1.2 scale: a correlated design and class imbalance), written as the
  program's model module writes them.
- ``transition_flops`` / ``job_flops``: the algorithm's work, not the HLO's.
- ``laplace``: the plain reference. Each subposterior
  p_m(θ) ∝ p(θ)^{1/M} ∏_{i∈shard m} p(y_i | x_i, θ) and the full posterior,
  by the Laplace expansion at the mode (Newton's method in float64 on the
  host): the covariance is the inverse Hessian, and the mean is the mode plus
  the expansion's skewness term ½ H⁻¹ a, a_j = Σ_kl ℓ_jkl (H⁻¹)_kl (Kass,
  Tierney & Kadane 1990). At the paper's signal strength the mode alone sits
  ~0.45 sd from a 5,000-row subposterior's mean; the corrected mean is within
  the chains' Monte Carlo error of it.
- ``reference``: ``laplace`` on the data, on the host.
- ``handoff``, ``summarize``, ``readings``, ``NUMBERS``: a job hands the
  combine stage its ``(M, T, d)`` subposterior draws; each job's
  subposterior and combined draws are reduced to their means and sds and
  compared with the reference by ``chipbench.check``'s six numbers.
- ``control``: a plain MALA chain per shard and the Gaussian product of the
  draws, in jnp at any dtype. Run in bfloat16 it is the check's control.
"""

from __future__ import annotations

import math
from types import SimpleNamespace
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import check

HIGHEST = jax.lax.Precision.HIGHEST

NUMBERS = {
    **{k: {"layer": "sampling", "scope": "job"} for k in ("sub_mean", "sub_sd")},
    **{k: {"layer": "combine", "scope": "job"} for k in ("comb_mean", "comb_sd")},
    "sub_mean_window": {"layer": "sampling", "scope": "window"},
    "comb_mean_window": {"layer": "combine", "scope": "window"},
}


def make_data(key: jax.Array, cfg: Dict) -> Dict[str, jax.Array]:
    """``{"x": (N, d), "y": (N,)}`` float32 on the default device."""
    n, d, design = int(cfg["N"]), int(cfg["d"]), cfg["design"]

    @jax.jit
    def build(key):
        if design == "iid":
            k_beta, k_x, k_y = jax.random.split(key, 3)
            beta = jax.random.normal(k_beta, (d,), jnp.float32)
            x = jax.random.normal(k_x, (n, d), jnp.float32)
            logits = jnp.matmul(x, beta, precision=HIGHEST)
        elif design == "covtype_like":
            k_beta, k_x, k_mix, k_y = jax.random.split(key, 4)
            beta = jax.random.normal(k_beta, (d,), jnp.float32) * 0.5
            base = jax.random.normal(k_x, (n, d), jnp.float32)
            mixer = jax.random.normal(k_mix, (d, d), jnp.float32) * (0.3 / math.sqrt(d))
            x = base + jnp.matmul(base, mixer, precision=HIGHEST)
            logits = jnp.matmul(x, beta, precision=HIGHEST) - 0.8
        else:
            raise ValueError(f"unknown design {design!r}")
        y = jax.random.bernoulli(k_y, jax.nn.sigmoid(logits)).astype(jnp.float32)
        return {"x": x, "y": y}

    return build(key)


def transition_flops(cfg: Dict) -> float:
    """One MALA transition of all M chains: one value-and-gradient of the
    log-likelihood over the real rows, X·θ then Xᵀ·r, 2·N·d FLOPs each."""
    return 4.0 * float(cfg["N"]) * float(cfg["d"])


def job_flops(cfg: Dict) -> float:
    """Warmup, burn-in and kept draws: every transition a job runs."""
    steps = int(cfg["warmup"]) + int(cfg["burn_in"]) + int(cfg["T"])
    return transition_flops(cfg) * steps


def shard_bounds(n: int, m: int) -> list:
    """Contiguous shards of ceil(N/M) rows, the last one short (the
    partition the configuration states)."""
    size = -(-n // m)
    return [(i * size, min(n, (i + 1) * size)) for i in range(m)]


def _log1pexp(z):
    return np.logaddexp(0.0, z)


def _newton(x, s, prior_prec, theta, iters=60):
    """Mean and covariance of exp(Σ log σ(s·xθ) − prior_prec‖θ‖²/2)."""
    d = x.shape[1]
    for _ in range(iters):
        z = s * (x @ theta)
        p = np.exp(-_log1pexp(z))  # σ(−z): d/dz log σ(z)
        g = x.T @ (s * p) - prior_prec * theta
        w = p * (1.0 - p)
        h = (x * w[:, None]).T @ x + prior_prec * np.eye(d)
        step = np.linalg.solve(h, g)
        theta = theta + step
        if np.max(np.abs(step)) < 1e-12 * (1.0 + np.max(np.abs(theta))):
            break
    z = s * (x @ theta)
    p = np.exp(-_log1pexp(z))  # σ(−z)
    w = p * (1.0 - p)
    cov = np.linalg.inv((x * w[:, None]).T @ x + prior_prec * np.eye(d))
    # ℓ'''(z) = −w(z)(1 − 2σ(z)) per row; a_j = Σ_i ℓ'''_i s_i x_ij (x_iᵀ H⁻¹ x_i)
    q = np.einsum("ij,jk,ik->i", x, cov, x)
    a = x.T @ (-w * (2.0 * p - 1.0) * s * q)
    return theta + 0.5 * cov @ a, cov


def laplace(x, y, cfg: Dict) -> Dict[str, np.ndarray]:
    """The reference: per-shard and full-posterior means and covariances.

    ``x``, ``y``: host arrays of the N real rows. Returns ``sub_mean (M, d)``,
    ``sub_cov (M, d, d)``, ``full_mean (d,)``, ``full_cov (d, d)``, float64.
    """
    x = np.asarray(x, np.float64)
    s = 2.0 * np.asarray(y, np.float64) - 1.0
    n, d = x.shape
    m = int(cfg["M"])
    prior_prec = 1.0 / float(cfg["prior_sigma"]) ** 2
    means, covs = [], []
    for lo, hi in shard_bounds(n, m):
        mu, cov = _newton(x[lo:hi], s[lo:hi], prior_prec / m, np.zeros(d))
        means.append(mu)
        covs.append(cov)
    means, covs = np.stack(means), np.stack(covs)
    # start the full-data solve from the subposteriors' Gaussian product
    precs = np.linalg.inv(covs)
    start = np.linalg.solve(precs.sum(0), np.einsum("mij,mj->i", precs, means))
    full_mean, full_cov = _newton(x, s, prior_prec, start)
    return {"sub_mean": means, "sub_cov": covs,
            "full_mean": full_mean, "full_cov": full_cov}


def reference(data: Dict[str, jax.Array], cfg: Dict) -> Dict[str, np.ndarray]:
    return laplace(np.asarray(data["x"]), np.asarray(data["y"]), cfg)


def handoff(sample) -> jax.Array:
    """The subposterior draws ``(M, T, d)`` of the program's sample result."""
    return sample.theta


def summarize(output, cfg: Dict) -> check.Moments:
    (combined,) = output.combine.values()
    return check.moments(np.asarray(output.sample.theta), np.asarray(combined.samples))


def readings(summaries: List[check.Moments], ref, cfg: Dict):
    return check.readings(summaries, ref)


def control(key: jax.Array, data: Dict[str, jax.Array], cfg: Dict, dtype) -> SimpleNamespace:
    """Plain MALA on each shard, then the Gaussian product, at ``dtype``.

    Returns a job's output in the program's place, as ``summarize`` reads
    it: ``sample.theta`` the subposterior draws ``(M, T, d)``, and
    ``combine`` one result whose ``samples`` are the combined draws
    ``(T, d)``. The chain (data, position, log density, gradient, proposal)
    is held in ``dtype``; only the step-size bookkeeping of the warmup is
    float32. The product's Cholesky algebra runs in float32, as jnp.linalg
    has no bfloat16 path.
    """
    x, y = data["x"], data["y"]
    n, d = x.shape
    m, t = int(cfg["M"]), int(cfg["T"])
    warmup, burn = int(cfg["warmup"]), int(cfg["burn_in"])
    size = -(-n // m)
    pad = m * size - n
    xs = jnp.concatenate([x, jnp.zeros((pad, d), x.dtype)]).reshape(m, size, d)
    ss = jnp.concatenate([2.0 * y - 1.0, jnp.zeros((pad,), y.dtype)]).reshape(m, size)
    valid = (jnp.arange(m * size) < n).reshape(m, size)
    prior_prec = 1.0 / (float(cfg["prior_sigma"]) ** 2 * m)
    target = 0.574

    def chain(xm, sm, vm, k):
        xm, sm, vm = xm.astype(dtype), sm.astype(dtype), vm.astype(dtype)

        def logp(th):
            z = sm * (xm @ th)
            return jnp.sum(vm * jax.nn.log_sigmoid(z)) - 0.5 * prior_prec * jnp.sum(th * th)

        vg = jax.value_and_grad(logp)

        def step(state, k, eps):
            th, lp, g = state
            k1, k2 = jax.random.split(k)
            e = eps.astype(dtype)
            prop = th + 0.5 * e * e * g + e * jax.random.normal(k1, th.shape, dtype)
            lp2, g2 = vg(prop)

            def logq(a, ga, b):
                r = b - a - 0.5 * e * e * ga
                return -jnp.sum(r * r) / (2.0 * e * e)

            log_r = (lp2 - lp + logq(prop, g2, th) - logq(th, g, prop)).astype(jnp.float32)
            acc = jnp.log(jax.random.uniform(k2)) < log_r
            keep = lambda a, b: jnp.where(acc, a, b)
            new = (keep(prop, th), keep(lp2, lp), keep(g2, g))
            return new, jnp.exp(jnp.minimum(log_r, 0.0))

        k0, kw, kb, kd = jax.random.split(k, 4)
        th0 = (0.01 * jax.random.normal(k0, (d,))).astype(dtype)
        state = (th0,) + tuple(vg(th0))

        def warm(carry, k):  # dual averaging on log ε (Hoffman & Gelman)
            state, log_eps, avg, hbar, i = carry
            state, a = step(state, k, jnp.exp(log_eps))
            i = i + 1.0
            hbar = (1 - 1 / (i + 10)) * hbar + (target - a) / (i + 10)
            log_eps = math.log(10 * 0.1) - jnp.sqrt(i) / 0.05 * hbar
            w = i ** -0.75
            return (state, log_eps, w * log_eps + (1 - w) * avg, hbar, i), None

        init = (state, jnp.log(0.1), 0.0, 0.0, 0.0)
        (state, _, avg, _, _), _ = jax.lax.scan(warm, init, jax.random.split(kw, warmup))
        eps = jnp.exp(avg)
        run = lambda s, k: (step(s, k, eps)[0], None)
        state, _ = jax.lax.scan(run, state, jax.random.split(kb, burn))
        keep = lambda s, k: (lambda s2: (s2, s2[0]))(step(s, k, eps)[0])
        _, draws = jax.lax.scan(keep, state, jax.random.split(kd, t))
        return draws

    sub = jax.jit(jax.vmap(chain))(xs, ss, valid, jax.random.split(key, m))
    mean = jnp.mean(sub, axis=1)
    cen = sub - mean[:, None]
    cov = jnp.einsum("mti,mtj->mij", cen, cen) / (t - 1)
    mean, cov = mean.astype(jnp.float32), cov.astype(jnp.float32)
    prec = jnp.linalg.inv(cov)
    full_cov = jnp.linalg.inv(prec.sum(0))
    full_mean = full_cov @ jnp.einsum("mij,mj->i", prec, mean)
    chol = jnp.linalg.cholesky(full_cov)
    z = jax.random.normal(jax.random.fold_in(key, 1), (t, d))
    combined = (full_mean + z @ chol.T).astype(dtype)
    return SimpleNamespace(sample=SimpleNamespace(theta=sub),
                           combine={"control": SimpleNamespace(samples=combined)})
