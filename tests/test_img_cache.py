"""The IMG combiners obtain their programs once per shape.

A registered IMG combiner runs two jitted programs, the weight-model build
(``combine.img.model``) and the chains (``combine.img.chain``). A second
call on equal shapes, with other data and another key, finds both in jit's
in-memory cache: its spans count no executable, and still count the sites.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bandwidth as bw
from repro.core.combiners import counts_or_full, get_combiner, run_img
from repro.core.combiners.img import _run_chain, semiparametric_model
from repro.utils import spans

M, T, D = 3, 96, 2

CASES = [
    ("nonparametric", {}),
    ("semiparametric", {}),
    ("semiparametric_w", {}),
    ("semiparametric", {"weight_eval": "kernel", "n_batch": 4}),
]
IDS = ["nonparametric", "semiparametric", "semiparametric_w", "semiparametric-kernel"]


def _cloud(seed):
    key = jax.random.PRNGKey(seed)
    centers = jnp.linspace(-0.5, 0.5, M)[:, None, None] * jnp.ones((1, 1, D))
    return centers + 0.4 * jax.random.normal(key, (M, T, D))


def _last(name):
    return [r for r in spans.records() if r.name == name][-1]


def _call(name, seed, n_draws=32, **options):
    """One combiner call on fresh data; its result and its two span records."""
    res = get_combiner(name)(
        jax.random.PRNGKey(100 + seed), _cloud(seed), n_draws, rescale=True, **options
    )
    jax.block_until_ready(res)
    return res, _last("combine.img.model"), _last("combine.img.chain")


@pytest.mark.parametrize("name,options", CASES, ids=IDS)
def test_the_second_call_obtains_no_program(name, options):
    _, model1, chain1 = _call(name, 1, **options)
    _, model2, chain2 = _call(name, 2, **options)
    assert model2 is not model1 and chain2 is not chain1
    assert model2.counters.get("executables", 0) == 0
    assert chain2.counters.get("executables", 0) == 0
    sites = -(-32 // options.get("n_batch", 1)) * options.get("n_batch", 1) * M
    assert chain1.counters["img_sites"] == chain2.counters["img_sites"] == sites


@pytest.mark.parametrize("name,options", CASES, ids=IDS)
def test_the_same_key_and_data_give_the_same_draws(name, options):
    first, _, _ = _call(name, 3, **options)
    second, _, chain = _call(name, 3, **options)
    assert chain.counters.get("executables", 0) == 0
    np.testing.assert_array_equal(np.asarray(first.samples), np.asarray(second.samples))
    assert float(first.acceptance_rate) == float(second.acceptance_rate)


@pytest.mark.parametrize("change", [{"n_draws": 41}, {"n_batch": 3}], ids=["n_draws", "n_batch"])
def test_a_new_shape_obtains_a_new_program(change):
    _call("semiparametric", 4)
    _, model, chain = _call("semiparametric", 5, **change)
    assert model.counters.get("executables", 0) == 0  # the model's shapes are the same
    assert chain.counters["executables"] >= 1


def test_run_img_finds_the_program_for_the_same_callables():
    """A caller's model and schedule are static: the same objects hit the
    cache, a fresh schedule obtains the program again."""
    samples = _cloud(6)
    counts = counts_or_full(samples, None)
    model = semiparametric_model(samples, counts)
    schedule = bw.annealed(D)

    def run(sched, seed):
        res = run_img(jax.random.PRNGKey(seed), samples, 24, model,
                      counts=counts, schedule=sched)
        jax.block_until_ready(res)
        return _last("combine.img.chain")

    run(schedule, 0)
    assert run(schedule, 1).counters.get("executables", 0) == 0
    assert run(bw.annealed(D), 2).counters["executables"] >= 1


def test_semiparametric_matches_the_eager_chain_and_the_gaussian_product():
    """The cached semiparametric program against the eager chain it
    replaces (the model's closures, one un-jitted scan) on Gaussian
    subposteriors, whose exact product is N(mean of the μ_m, σ²/M I)."""
    m, t, d, sigma, n = 6, 500, 3, 0.5, 1500
    key = jax.random.PRNGKey(7)
    mus = 0.3 * jax.random.normal(key, (m, 1, d))
    samples = mus + sigma * jax.random.normal(jax.random.fold_in(key, 1), (m, t, d))
    k_draw = jax.random.PRNGKey(8)

    cached = get_combiner("semiparametric")(k_draw, samples, n, rescale=True).samples
    counts = counts_or_full(samples, None)
    schedule = bw.annealed(d, scale=bw.pooled_scale(samples))
    eager, _ = _run_chain(k_draw, samples, counts, n, schedule,
                          semiparametric_model(samples, counts))

    mean = np.asarray(jnp.mean(mus, axis=0))[0]
    sd = sigma / np.sqrt(m)
    for draws in (np.asarray(cached), np.asarray(eager)):
        assert np.isfinite(draws).all()
        np.testing.assert_allclose(draws.mean(axis=0), mean, atol=0.2)
        np.testing.assert_allclose(draws.std(axis=0), sd, rtol=0.35)
    np.testing.assert_allclose(np.asarray(cached).mean(axis=0),
                               np.asarray(eager).mean(axis=0), atol=0.2)
    np.testing.assert_allclose(np.asarray(cached).std(axis=0),
                               np.asarray(eager).std(axis=0), rtol=0.35)
