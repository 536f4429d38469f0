"""repro.api: RunSpec validation/serialization, Pipeline stages + determinism,
run_matrix compile caching, and the platform-aware Pallas interpret resolver."""

import jax
import jax.numpy as jnp
import pytest

from repro.api import Pipeline, RunSpec, run_matrix
from repro.models.bayes import linear_gaussian as lg

# small-but-real scenario shared by the pipeline tests (linear: every stage
# has a closed-form oracle and the default mala sampler exercises warmup)
SPEC = RunSpec(
    model="linear",
    M=4,
    T=60,
    warmup=30,
    n=512,
    seed=3,
    groundtruth_T=120,
    combiner=("parametric", "nonparametric"),
    score_metric="logl2",
)


# ---------------------------------------------------------------------------
# RunSpec
# ---------------------------------------------------------------------------


def test_runspec_validates_against_all_three_registries():
    with pytest.raises(KeyError, match="unknown model"):
        RunSpec(model="nope").validate()
    with pytest.raises(KeyError, match="unknown sampler"):
        RunSpec(model="linear", sampler="nope").validate()
    with pytest.raises(KeyError, match="unknown combiner"):
        RunSpec(model="linear", combiner="nope").validate()


def test_runspec_gibbs_needs_model_surface():
    # gmm registers no Gibbs blocks — the spec must fail fast, not at trace
    with pytest.raises(ValueError, match="Gibbs"):
        RunSpec(model="gmm", sampler="gibbs").validate()
    RunSpec(model="linear", sampler="gibbs").validate()  # conjugate blocks exist


def test_runspec_field_validation():
    with pytest.raises(ValueError, match="step_size"):
        RunSpec(model="linear", step_size=0.0)
    with pytest.raises(ValueError, match="score_metric"):
        RunSpec(model="linear", score_metric="l3")
    with pytest.raises(ValueError, match="must be >="):
        RunSpec(model="linear", M=0)


def test_runspec_json_roundtrip_and_spec_id():
    spec = RunSpec(
        model="poisson", sampler="gibbs", M=8, seed=7,
        combiner_options={"n_batch": 4}, combiner=["parametric"],
    )
    again = RunSpec.from_json(spec.to_json())
    assert again == spec
    assert again.spec_id == spec.spec_id
    # content hash is sensitive to every field, stable under identity
    assert spec.spec_id != RunSpec(model="poisson", sampler="gibbs", M=8, seed=8,
                                   combiner_options={"n_batch": 4},
                                   combiner=["parametric"]).spec_id
    with pytest.raises(ValueError, match="unknown RunSpec fields"):
        RunSpec.from_dict({"model": "linear", "bogus": 1})


def test_runspec_is_hashable_static_pytree():
    spec = RunSpec(model="linear", sampler_options={"a": 1})
    assert hash(spec) == hash(RunSpec(model="linear", sampler_options={"a": 1}))
    leaves, treedef = jax.tree_util.tree_flatten(spec)
    assert leaves == []  # all-static: safe inside jitted closures
    assert jax.tree_util.tree_unflatten(treedef, leaves) == spec


def test_executable_signature_groups_seed_and_step_sweeps():
    base = RunSpec(model="linear", T=50, warmup=10, n=256)
    assert base.executable_signature() == \
        RunSpec(model="linear", T=50, warmup=10, n=256, seed=9,
                step_size=0.3, combiner="parametric").executable_signature()
    assert base.executable_signature() != \
        RunSpec(model="linear", T=51, warmup=10, n=256).executable_signature()


# ---------------------------------------------------------------------------
# Pipeline stages
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def pipeline():
    pipe = Pipeline(SPEC)
    pipe.run()
    return pipe


def test_pipeline_stage_artifacts(pipeline):
    sharded = pipeline.partition()
    assert jax.tree.leaves(sharded.shards)[0].shape[0] == SPEC.M
    assert sharded.counts.shape == (SPEC.M,)

    draws = pipeline.sample()
    assert draws.theta.shape == (SPEC.M, SPEC.T, 10)
    assert draws.complete and draws.t_done == SPEC.T
    assert bool(jnp.all(jnp.isfinite(draws.theta)))

    combined = pipeline.combine()
    assert set(combined) == {"parametric", "nonparametric"}
    for res in combined.values():
        assert res.samples.shape == (SPEC.T, 10)

    board = pipeline.score()
    assert board.metric == "logL2"
    assert set(board.errors) == set(combined)
    assert all(v == v for v in board.errors.values())  # finite, no NaN
    assert board.spec_id == SPEC.spec_id


def test_pipeline_parametric_recovers_closed_form(pipeline):
    """The linear model is the exactness oracle: the parametric product must
    land on the closed-form posterior mean (Thm 3.1 regime)."""
    posterior = lg.posterior_moments(pipeline.partition().data)
    samples = pipeline.combine()["parametric"].samples
    err = float(jnp.linalg.norm(samples.mean(0) - posterior.mean))
    scale = float(jnp.linalg.norm(posterior.mean))
    assert err < 0.25 * scale


def test_same_spec_is_bitwise_deterministic(pipeline):
    """Same RunSpec ⇒ bitwise-identical artifacts, stage by stage."""
    other = Pipeline(SPEC)
    assert bool(jnp.all(other.sample().theta == pipeline.sample().theta))
    ours, theirs = pipeline.combine(), other.combine()
    for name in ours:
        assert bool(jnp.all(ours[name].samples == theirs[name].samples)), name
    assert other.score().errors == pipeline.score().errors


def test_max_steps_requires_checkpoint_dir():
    with pytest.raises(ValueError, match="checkpoint_dir"):
        Pipeline(SPEC).sample(max_steps=10)


def test_max_steps_requires_a_chunk_cadence(tmp_path):
    # sessions advance in whole chunks: a budget the cadence can't express
    # must raise instead of silently doing zero durable work
    with pytest.raises(ValueError, match="durable progress"):
        Pipeline(SPEC, checkpoint_dir=tmp_path).sample(max_steps=10)
    with pytest.raises(ValueError, match="durable progress"):
        Pipeline(SPEC, checkpoint_dir=tmp_path, checkpoint_every=20).sample(
            max_steps=10
        )


def test_mesh_specs_with_checkpointing_need_devices_not_a_fork(tmp_path):
    """Mesh + checkpointing is supported since the backend unification —
    the only remaining failure mode is a genuine resource problem, and the
    error must say how to fix it (the old path raised unconditionally)."""
    spec = RunSpec(**{**SPEC.to_dict(), "mesh_shape": (4, 1)})
    with pytest.raises(ValueError, match="devices but only"):
        Pipeline(spec, checkpoint_dir=tmp_path).sample()


def test_checkpoint_every_requires_a_dir():
    with pytest.raises(ValueError, match="persist nothing"):
        Pipeline(SPEC, checkpoint_every=20)


def test_run_matrix_rejects_mesh_specs():
    spec = RunSpec(**{**SPEC.to_dict(), "mesh_shape": (4, 1)})
    with pytest.raises(ValueError, match="vmap backend only"):
        run_matrix([spec])


def test_sampler_options_reach_the_kernel_factory():
    """RunSpec.sampler_options must actually change the kernel, not just the
    spec_id — hmc trajectories of length 1 vs 10 give different draws."""
    from repro.api import sample_subposteriors
    from repro.models.bayes import get_model

    model = get_model("linear")
    key = jax.random.PRNGKey(0)
    data, _ = model.generate_data(key, 256)
    kw = dict(sampler="hmc", warmup=0, burn_in=5, step_size=0.05)
    base = sample_subposteriors(key, model, data, 2, 10, **kw)
    short = sample_subposteriors(
        key, model, data, 2, 10,
        sampler_options={"num_integration_steps": 1}, **kw,
    )
    assert not bool(jnp.all(base.theta == short.theta))
    # unknown keys are dropped per the registry filter convention, not fatal
    ignored = sample_subposteriors(
        key, model, data, 2, 10,
        sampler_options={"not_an_option": 1}, **kw,
    )
    assert bool(jnp.all(base.theta == ignored.theta))


def test_sample_subposteriors_is_the_chunk_driver_with_one_chunk():
    """The one-call stage runs the chunk driver: bitwise its draws, one
    ``sample.stage`` record per call, and a second call with the same
    statics obtains no executable (its programs are cached per backend)."""
    from repro.api import sample_subposteriors, stream_sample
    from repro.models.bayes import get_model
    from repro.utils import spans

    model = get_model("linear")
    data, _ = model.generate_data(jax.random.PRNGKey(0), 256)
    kw = dict(sampler="mala", warmup=8, burn_in=4, step_size=0.05)
    stages = lambda: [r for r in spans.records() if r.name == "sample.stage"]

    want = stream_sample(
        jax.random.PRNGKey(1), model, data, 4, 20, chunk_size=0, **kw
    ).result
    n0 = len(stages())
    got = sample_subposteriors(jax.random.PRNGKey(1), model, data, 4, 20, **kw)
    again = sample_subposteriors(jax.random.PRNGKey(2), model, data, 4, 20, **kw)
    recs = stages()
    assert len(recs) == n0 + 2  # exactly one stage record per call
    assert got.backend == want.backend == "vmap[chunked]"
    assert got.collectives_checked is None
    assert bool(jnp.all(got.theta == want.theta))
    assert bool(jnp.all(got.accept == want.accept))
    assert not bool(jnp.all(again.theta == got.theta))  # a new key's chains
    assert recs[-1].counters.get("executables", 0) == 0
    assert recs[-1].counters["steps"] == 8 + 4 + 20


_IMPORTS_SCRIPT = """
import sys
import jax
from repro.api import Pipeline, RunSpec

spec = RunSpec(model="poisson", sampler="gibbs", M=2, T=20, warmup=0, n=200,
               groundtruth_T=40, combiner=("parametric", "nonparametric"))
board = Pipeline(spec).run()
assert set(board.errors) == {"parametric", "nonparametric"}
print("LOADED=" + ",".join(sorted(
    m for m in sys.modules
    if m.startswith(("repro.models.lm", "repro.distributed"))
)))
"""


def test_a_pipeline_run_imports_no_lm_or_distributed_module():
    """The EP-MCMC core (sample, combine, score) stands below the LM side
    path: no module under ``repro.api`` imports ``repro.distributed``, and
    a whole Pipeline run leaves ``repro.models.lm`` unimported."""
    import os
    import pathlib
    import re
    import subprocess
    import sys

    import repro.api

    api_dir = pathlib.Path(repro.api.__file__).parent
    for path in sorted(api_dir.glob("*.py")):
        assert not re.search(
            r"^\s*(from|import)\s+repro\.distributed", path.read_text(), re.M
        ), f"{path.name} imports repro.distributed"

    src = str(api_dir.parents[1])
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        PYTHONPATH=src + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    out = subprocess.run(
        [sys.executable, "-c", _IMPORTS_SCRIPT], capture_output=True,
        text=True, env=env, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("LOADED=")][-1]
    assert line == "LOADED=", line


# ---------------------------------------------------------------------------
# run_matrix: compile-cache accounting + Pipeline agreement
# ---------------------------------------------------------------------------


def test_run_matrix_compiles_once_per_signature(tmp_path):
    """8 specs spanning 2 signatures (2 models × 2 seeds × 2 step sizes)
    must build exactly 2 sampling executables — seeds and step sizes are
    runtime inputs, not compile triggers."""
    specs = [
        RunSpec(model=m, sampler="mala", combiner="parametric", M=4, T=40,
                warmup=30, n=256, seed=seed, step_size=step,
                groundtruth_T=80, score_metric="logl2")
        for m in ("linear", "poisson")
        for seed in (0, 1)
        for step in (0.1, 0.2)
    ]
    assert len(specs) == 8
    assert len({s.executable_signature() for s in specs}) == 2
    res = run_matrix(specs, json_path=str(tmp_path / "matrix.json"))
    assert res.n_specs == 8
    assert res.n_executables == 2  # the compile-cache acceptance criterion
    assert res.n_groundtruth_executables == 2
    assert len(res.rows) == 8
    assert all(r["error"] == r["error"] for r in res.rows)
    assert (tmp_path / "matrix.json").exists()
    assert "8 cells on vmap, 2 sampling executables" in res.table()


def test_run_matrix_agrees_with_pipeline(pipeline):
    """A matrix cell and a standalone Pipeline over the same spec share the
    RNG discipline end to end — same scoreboard numbers (to the last-ulp
    fusion tolerance of tracing step_size instead of closing over it)."""
    res = run_matrix([SPEC])
    matrix_errors = {r["combiner"]: r["error"] for r in res.rows}
    board = pipeline.score().errors
    assert set(matrix_errors) == set(board)
    for name in board:
        assert matrix_errors[name] == pytest.approx(board[name], rel=1e-4)


# ---------------------------------------------------------------------------
# linear-Gaussian Gibbs surface (scenario-matrix feasibility)
# ---------------------------------------------------------------------------


def test_linear_gibbs_blocks_recover_closed_form_posterior():
    key = jax.random.PRNGKey(0)
    data, _ = lg.generate_data(key, 2000, 6)
    post = lg.posterior_moments(data)
    from repro.samplers import get_sampler
    from repro.samplers.base import run_chain

    kern = get_sampler("gibbs")(None, block_updates=lg.gibbs_blocks(data, 1))
    pos, info = jax.jit(
        lambda k: run_chain(k, kern, jnp.zeros(6), 2000, burn_in=200)
    )(jax.random.fold_in(key, 1))
    assert bool(jnp.all(info.is_accepted))  # exact conditionals: no MH moves
    assert float(jnp.linalg.norm(pos.mean(0) - post.mean)) < 0.01
    cov_err = float(jnp.linalg.norm(jnp.cov(pos.T) - post.cov))
    assert cov_err < 0.25 * float(jnp.linalg.norm(post.cov))


# ---------------------------------------------------------------------------
# platform-aware Pallas interpret resolver
# ---------------------------------------------------------------------------


def test_default_interpret_platform_and_env(monkeypatch):
    from repro.kernels import default_interpret

    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    # CPU rig: interpret mode on by default (False only on a real TPU)
    assert default_interpret() is (jax.default_backend() != "tpu")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert default_interpret() is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "true")
    assert default_interpret() is True
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "maybe")
    with pytest.raises(ValueError, match="REPRO_PALLAS_INTERPRET"):
        default_interpret()
