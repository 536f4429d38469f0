"""The logistic likelihood's fused value-and-gradient path.

On a TPU the gradient samplers' ``value_and_grad`` of the logistic
likelihood comes from one pass of ``kernels/logreg_loglik`` over a
lane-dense copy of the shard, made once per program (the model's
``shard_form``). Here, on the CPU, the kernel runs in interpret mode, and the
tests that need the TPU's choice make it by patching the platform test.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.logreg_loglik.ops as ops
from repro.api.sampling import (
    loglik_fused,
    make_shard_kernel,
    run_shard_chain,
)
from repro.api.streaming import stream_sample
from repro.core.subposterior import make_subposterior_logpdf, partition_data
from repro.kernels.logreg_loglik import lane_dense, logreg_loglik_grad_ref
from repro.kernels.logreg_loglik.kernel import logreg_loglik_grad_kernel
from repro.models.bayes import get_model
from repro.models.bayes.logistic_regression import log_lik, log_prior, shard_form
from repro.utils import spans

GRAD_TOL = dict(rtol=1e-5, atol=1e-3)


@pytest.fixture
def on_tpu(monkeypatch):
    """The selection rule as it reads on a TPU; the kernel stays interpreted."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)


def _data(n, d, seed=0):
    model = get_model("covtype" if d == 54 else "logreg")
    data, _ = model.generate_data(jax.random.PRNGKey(seed), n)
    return data


def _theta(d, seed=1):
    return 0.3 * jax.random.normal(jax.random.PRNGKey(seed), (d,))


@pytest.mark.parametrize("d", [50, 54])
@pytest.mark.parametrize("n", [1500, 2048])
def test_fused_value_and_grad_match_the_jnp_form(d, n):
    data, theta = _data(n, d), _theta(d)
    form = shard_form(data)
    assert form["ut"].shape == (d, n)
    l, g = jax.jit(jax.value_and_grad(lambda th: log_lik(th, form)))(theta)
    lr, gr = jax.value_and_grad(lambda th: log_lik(th, data))(theta)
    np.testing.assert_allclose(l, lr, rtol=1e-5)
    np.testing.assert_allclose(g, gr, **GRAD_TOL)
    # the undifferentiated value is the jnp form itself
    assert float(jax.jit(log_lik)(theta, form)) == float(jax.jit(log_lik)(theta, data))


@pytest.mark.parametrize("n", [1000, 1024, 700])
def test_kernel_masks_the_tail_of_the_last_block(n):
    """Several blocks a row, a last one cut short: the columns past N hold
    NaN in interpret mode (garbage on the chip) and must not count."""
    d = 54
    x = jax.random.normal(jax.random.PRNGKey(n), (n, d))
    s = jnp.where(jax.random.uniform(jax.random.PRNGKey(n + 1), (n,)) < 0.5, 1.0, -1.0)
    beta = _theta(d)
    l, g = logreg_loglik_grad_kernel(
        lane_dense(x, s), beta[:, None], block_n=256, sub=128, interpret=True
    )
    lr, gr = logreg_loglik_grad_ref(x, s, beta)
    np.testing.assert_allclose(l[0, 0], lr, rtol=1e-5)
    np.testing.assert_allclose(g[:, 0], gr, **GRAD_TOL)


def test_padded_shard_with_count():
    """The whole-shard term reads the form; the padding correction slices the
    final row from the raw rows."""
    data = _data(2 * 1100 - 3, 50)
    shards, counts = partition_data(data, 2, pad=True)
    assert int(counts[1]) == 1098 < shards["x"].shape[1]  # two pad rows
    shard = jax.tree.map(lambda a: a[1], shards)
    theta = _theta(50)
    want = make_subposterior_logpdf(log_prior, log_lik, shard, 2, count=counts[1])
    got = make_subposterior_logpdf(
        log_prior, log_lik, shard, 2, count=counts[1], form=shard_form(shard)
    )
    l, g = jax.jit(jax.value_and_grad(got))(theta)
    lr, gr = jax.value_and_grad(want)(theta)
    np.testing.assert_allclose(l, lr, rtol=1e-5)
    np.testing.assert_allclose(g, gr, **GRAD_TOL)


def test_vmap_over_chains():
    data = _data(3 * 1200, 54)
    shards = partition_data(data, 3)
    thetas = 0.3 * jax.random.normal(jax.random.PRNGKey(5), (3, 54))
    vg = jax.value_and_grad(log_lik)
    ls, gs = jax.jit(jax.vmap(lambda th, sh: vg(th, shard_form(sh))))(thetas, shards)
    lr, gr = jax.vmap(vg)(thetas, shards)
    np.testing.assert_allclose(ls, lr, rtol=1e-5)
    np.testing.assert_allclose(gs, gr, **GRAD_TOL)


def _mala_draws(model, shards, counts, m):
    sk = make_shard_kernel(model, 2, "mala", use_counts=True)
    run = jax.jit(lambda sh, c, k: run_shard_chain(
        sk, sh, c, k, num_samples=12, burn_in=3, warmup=0, step_size=0.01))
    shard = jax.tree.map(lambda a: a[m], shards)
    return run(shard, counts[m], jax.random.PRNGKey(7))


def test_a_short_mala_chain_matches_the_jnp_path(monkeypatch):
    model = get_model("logreg")
    data, _ = model.generate_data(jax.random.PRNGKey(2), 2 * 1030 - 1)
    shards, counts = partition_data(data, 2, pad=True)
    theta_jnp, acc_jnp = _mala_draws(model, shards, counts, 1)
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    theta_fused, acc_fused = _mala_draws(model, shards, counts, 1)
    assert float(acc_fused) == float(acc_jnp) > 0
    np.testing.assert_allclose(theta_fused, theta_jnp, rtol=1e-4, atol=1e-5)


def test_the_path_is_chosen_by_platform_and_rows(monkeypatch):
    logreg, poisson = get_model("logreg"), get_model("poisson")
    big = 10 * ops.MIN_KERNEL_N
    # on the CPU the jnp form runs whatever the size
    assert not ops.use_fused(big)
    assert not loglik_fused(logreg, "mala", big)
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    assert ops.use_fused(ops.MIN_KERNEL_N) and not ops.use_fused(ops.MIN_KERNEL_N - 1)
    assert not ops.use_fused(1)  # the padding correction's final row
    assert not ops.use_fused(256)  # SGLD's minibatch
    assert loglik_fused(logreg, "mala", big) and loglik_fused(logreg, "hmc", big)
    assert not loglik_fused(logreg, "rwmh", big)  # no gradient
    assert not loglik_fused(logreg, "sgld", big)
    assert not loglik_fused(poisson, "mala", big)  # no fused pass


def _stage_counters(n, reps=1):
    model = get_model("logreg")
    data, _ = model.generate_data(jax.random.PRNGKey(3), n)
    shards, counts = partition_data(data, 2, only=model.shard_keys, pad=True)
    out = []
    for j in range(reps):
        stream_sample(jax.random.PRNGKey(j), model, data, 2, 10, warmup=4,
                      burn_in=2, shards=shards, counts=counts)
        out.append([r for r in spans.records() if r.name == "sample.stage"][-1].counters)
    return out


def test_loglik_fused_reads_zero_on_cpu():
    (counters,) = _stage_counters(2 * 1030)
    assert counters["steps"] == 4 + 2 + 10
    assert counters["loglik_fused"] == 0


def test_loglik_fused_counts_every_transition_of_a_cached_program(on_tpu):
    first, second = _stage_counters(2 * 1031, reps=2)
    assert first["loglik_fused"] == second["loglik_fused"] == 4 + 2 + 10
    assert second.get("executables", 0) == 0  # the second job compiled nothing
