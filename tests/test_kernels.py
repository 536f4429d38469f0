"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.img_weights import img_log_weights, img_log_weights_ref
from repro.kernels.kde_density import kde_log_density, kde_log_density_ref
from repro.kernels.logreg_loglik import lane_dense, logreg_loglik_grad, logreg_loglik_grad_ref


@pytest.mark.parametrize("P,M,d", [(300, 10, 50), (256, 4, 512), (100, 20, 7), (64, 2, 1), (65, 3, 130)])
@pytest.mark.parametrize("h", [0.3, 1.0])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_img_weights_matches_ref(P, M, d, h, dtype):
    theta = jax.random.normal(jax.random.PRNGKey(P + d), (P, M, d), dtype)
    got = img_log_weights(theta, h)
    want = img_log_weights_ref(theta, h)
    rtol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=rtol, atol=5e-3)
    assert got.dtype == jnp.float32


def test_img_weights_matches_algorithm1_oracle():
    """The kernel's weight must equal combine.log_weight_bruteforce (Eq 3.5)."""
    from repro.core.combine import log_weight_bruteforce

    theta = jax.random.normal(jax.random.PRNGKey(0), (128, 8, 5))
    h = jnp.asarray(0.7)
    got = img_log_weights(theta, h)
    want = jax.vmap(lambda t: log_weight_bruteforce(t, h))(theta)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("N,d", [(5000, 50), (1024, 54), (100, 3), (1025, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_logreg_kernel_matches_ref(N, d, dtype):
    k = jax.random.PRNGKey(N + d)
    kx, kb, ky = jax.random.split(k, 3)
    X = jax.random.normal(kx, (N, d), dtype)
    beta = (jax.random.normal(kb, (d,)) * 0.3).astype(dtype)
    y = jnp.where(jax.random.uniform(ky, (N,)) < 0.5, 1.0, -1.0)
    l, g = logreg_loglik_grad(lane_dense(X, y), beta)
    lr, gr = logreg_loglik_grad_ref(X, y, beta)
    rtol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(l, lr, rtol=rtol)
    np.testing.assert_allclose(g, gr, rtol=max(rtol, 1e-4), atol=0.3 if dtype == jnp.bfloat16 else 1e-3)


def test_logreg_kernel_multichain():
    k = jax.random.PRNGKey(0)
    X = jax.random.normal(k, (2048, 20))
    y = jnp.where(jax.random.uniform(jax.random.fold_in(k, 1), (2048,)) < 0.5, 1.0, -1.0)
    B = jax.random.normal(jax.random.fold_in(k, 2), (20, 5)) * 0.2
    ut = lane_dense(X, y)
    ls, gs = jax.vmap(lambda b: logreg_loglik_grad(ut, b))(B.T)
    for c in range(5):
        lc, gc = logreg_loglik_grad_ref(X, y, B[:, c])
        np.testing.assert_allclose(ls[c], lc, rtol=1e-5)
        np.testing.assert_allclose(gs[c], gc, rtol=1e-4, atol=1e-3)


def test_logreg_kernel_grad_is_true_gradient():
    """∇ from the fused kernel == autodiff of the likelihood."""
    k = jax.random.PRNGKey(3)
    X = jax.random.normal(k, (512, 9))
    y = jnp.where(jax.random.uniform(jax.random.fold_in(k, 1), (512,)) < 0.5, 1.0, -1.0)
    beta = jax.random.normal(jax.random.fold_in(k, 2), (9,)) * 0.5
    _, g = logreg_loglik_grad(lane_dense(X, y), beta)
    g_ad = jax.grad(lambda b: logreg_loglik_grad_ref(X, y, b)[0])(beta)
    np.testing.assert_allclose(g, g_ad, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("nq,ns,d", [(300, 700, 10), (256, 512, 2), (100, 999, 54), (64, 64, 1)])
@pytest.mark.parametrize("h", [0.2, 1.0, 3.0])
def test_kde_density_matches_ref(nq, ns, d, h):
    k = jax.random.PRNGKey(nq * ns)
    q = jax.random.normal(k, (nq, d))
    s = jax.random.normal(jax.random.fold_in(k, 1), (ns, d))
    got = kde_log_density(q, s, h)
    want = kde_log_density_ref(q, s, h)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_kde_density_matches_metrics_kde():
    """Kernel and the metrics-module KDE must agree (two independent paths)."""
    from repro.core.metrics import kde_logpdf

    k = jax.random.PRNGKey(7)
    q = jax.random.normal(k, (128, 6))
    s = jax.random.normal(jax.random.fold_in(k, 1), (400, 6))
    np.testing.assert_allclose(
        kde_log_density(q, s, 0.8), kde_logpdf(q, s, 0.8), rtol=1e-5, atol=1e-4
    )


def test_kde_density_is_normalized_density():
    """∫ p̂ ≈ 1 sanity via Monte Carlo over a wide box (d=1)."""
    s = jax.random.normal(jax.random.PRNGKey(0), (500, 1))
    grid = jnp.linspace(-8, 8, 2001)[:, None]
    logp = kde_log_density(grid, s, 0.5)
    integral = jnp.trapezoid(jnp.exp(logp), grid[:, 0])
    assert abs(float(integral) - 1.0) < 1e-2
