"""Deprecation hygiene: the legacy entry points still work — and say so.

``repro.core.combine`` and the ``mcmc_run`` module internals moved behind
``repro.core.combiners`` / ``repro.api``; the shims must emit a
``DeprecationWarning`` pointing at the replacement while returning
registry-identical results.
"""

import warnings

import jax
import jax.numpy as jnp
import pytest


def _samples(key=0, M=4, T=50, d=3):
    return jax.random.normal(jax.random.PRNGKey(key), (M, T, d))


def test_combine_shim_warns_and_matches_registry():
    from repro.core import combine

    with pytest.warns(DeprecationWarning, match="repro.core.combiners"):
        parametric = combine.parametric
    # forwarded names ARE the registry objects — identical by construction
    import repro.core.combiners as combiners

    assert parametric is combiners.parametric


def test_combine_shim_img_wrappers_match_registry_bitwise():
    from repro.core import combine
    from repro.core.combiners import get_combiner

    samples = _samples()
    key = jax.random.PRNGKey(1)
    with pytest.warns(DeprecationWarning, match="get_combiner"):
        legacy = combine.nonparametric_img(key, samples, 20, rescale=True)
    registry = get_combiner("nonparametric")(key, samples, 20, rescale=True)
    assert bool(jnp.all(legacy.samples == registry.samples))

    with pytest.warns(DeprecationWarning, match="get_combiner"):
        legacy = combine.semiparametric_img(key, samples, 20, rescale=True)
    registry = get_combiner("semiparametric")(key, samples, 20, rescale=True)
    assert bool(jnp.all(legacy.samples == registry.samples))


def test_combine_shim_unknown_attribute_raises():
    from repro.core import combine

    with pytest.raises(AttributeError):
        combine.does_not_exist


def test_mcmc_run_internals_warn_and_forward_to_api():
    from repro.launch import mcmc_run
    from repro.api import sampling

    with pytest.warns(DeprecationWarning, match="repro.api"):
        assert mcmc_run.sample_subposteriors is sampling.sample_subposteriors
    with pytest.warns(DeprecationWarning, match="repro.api"):
        assert mcmc_run.SampleResult is sampling.SampleResult
    with pytest.warns(DeprecationWarning, match="repro.api"):
        assert mcmc_run.LOG_L2_DIM == 40


def test_legacy_sample_subposteriors_import_still_runs():
    """The moved engine keeps its behavior through the shim (the
    test_multidevice subprocess relied on this exact call shape)."""
    from repro.models.bayes import get_model

    with pytest.warns(DeprecationWarning):
        from repro.launch.mcmc_run import sample_subposteriors  # noqa: F401
    model = get_model("poisson")
    data, _ = model.generate_data(jax.random.PRNGKey(0), 400)
    res = sample_subposteriors(
        jax.random.PRNGKey(1), model, data, 4, 20, warmup=5, step_size=0.1
    )
    assert res.theta.shape == (4, 20, 2)
    assert bool(jnp.all(jnp.isfinite(res.theta)))
