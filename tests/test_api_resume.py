"""Checkpoint/resume of the sampling stage.

The guarantee under test: an interrupted-then-resumed sampling stage
reproduces the uninterrupted run **bitwise**. Chunk boundaries are global
(k·checkpoint_every), sessions advance in whole chunks, and the per-step RNG
keys derive from the spec seed alone — so resume replays exactly the same
chunk programs on the same inputs as a run that was never interrupted. The
chunked driver is additionally cross-checked against the plain stage's one
T-draw chunk (numerically: XLA may fuse one big scan differently at the
last ulp, which is why the bitwise contract is defined against the
uninterrupted run at the same cadence).
"""

import jax.numpy as jnp
import pytest

from repro.api import Pipeline, RunSpec
from repro.checkpoint import latest_step

SPEC = RunSpec(
    model="linear",
    M=4,
    T=60,
    warmup=30,  # adaptive mala warmup: resume must rebuild from persisted ε
    n=512,
    seed=3,
    groundtruth_T=120,
    combiner=("parametric",),
    score_metric="logl2",
)


def test_interrupt_resume_is_bitwise_identical(tmp_path):
    # uninterrupted references: the same cadence (the bitwise contract) and
    # one T-draw chunk (numerical cross-check of the chunking)
    uninterrupted = Pipeline(
        SPEC, checkpoint_dir=tmp_path / "ref", checkpoint_every=20
    ).sample()
    plain = Pipeline(SPEC).sample()

    # interrupted run: stopped at the t=20 chunk boundary (a 30-draw budget
    # rounds down — partial-chunk work is lost on preemption anyway)
    p1 = Pipeline(SPEC, checkpoint_dir=tmp_path / "run", checkpoint_every=20)
    partial = p1.sample(max_steps=30)
    assert not partial.complete
    assert partial.t_done == 20
    assert partial.theta.shape == (SPEC.M, 20, 10)
    assert latest_step(tmp_path / "run") == 20  # kernel state persisted

    # fresh Pipeline (new process in spirit): resumes from the checkpoint
    p2 = Pipeline(SPEC, checkpoint_dir=tmp_path / "run", checkpoint_every=20)
    full = p2.sample()
    assert full.complete and full.t_done == SPEC.T
    assert full.backend == "vmap[resumable]"

    # the acceptance criterion: resume ≡ uninterrupted, bitwise
    assert bool(jnp.all(full.theta == uninterrupted.theta))
    assert bool(jnp.all(full.accept == uninterrupted.accept))
    assert bool(jnp.all(partial.theta == uninterrupted.theta[:, :20]))
    # and the chunked trajectory is the one-chunk trajectory numerically
    assert bool(jnp.allclose(full.theta, plain.theta, atol=1e-5))


def test_completed_checkpoint_short_circuits_resampling(tmp_path):
    p1 = Pipeline(SPEC, checkpoint_dir=tmp_path)
    ref = p1.sample()
    assert latest_step(tmp_path) == SPEC.T
    p2 = Pipeline(SPEC, checkpoint_dir=tmp_path)
    again = p2.sample()  # restores the finished stage, runs zero chunks
    assert again.complete
    assert bool(jnp.all(again.theta == ref.theta))
    # and the downstream stages run off the restored artifact
    board = p2.score()
    assert all(v == v for v in board.errors.values())


def test_mid_run_checkpoint_is_cadence_locked(tmp_path):
    """Resuming an unfinished run at a different checkpoint_every would shift
    the global chunk boundaries and void the bitwise guarantee — reject it.
    (A *finished* checkpoint has no tail to replay: any cadence may read it.)"""
    Pipeline(SPEC, checkpoint_dir=tmp_path, checkpoint_every=20).sample(
        max_steps=20
    )
    with pytest.raises(ValueError, match="bitwise-resume"):
        Pipeline(SPEC, checkpoint_dir=tmp_path, checkpoint_every=10).sample()
    with pytest.raises(ValueError, match="bitwise-resume"):
        Pipeline(SPEC, checkpoint_dir=tmp_path).sample()  # default cadence 0
    # original cadence resumes fine, and the finished artifact is readable
    # under any cadence
    Pipeline(SPEC, checkpoint_dir=tmp_path, checkpoint_every=20).sample()
    done = Pipeline(SPEC, checkpoint_dir=tmp_path).sample()
    assert done.complete


def test_checkpoint_dir_is_spec_locked(tmp_path):
    Pipeline(SPEC, checkpoint_dir=tmp_path, checkpoint_every=20).sample(
        max_steps=20
    )
    other = RunSpec(**{**SPEC.to_dict(), "seed": SPEC.seed + 1})
    with pytest.raises(ValueError, match="refusing to resume"):
        Pipeline(other, checkpoint_dir=tmp_path).sample()


def test_resumable_supports_gibbs_extended_positions(tmp_path):
    """Gibbs positions are extended pytrees (shard-local latents) — the
    chunked driver must checkpoint/restore them and extract shared θ."""
    spec = RunSpec(
        model="poisson", sampler="gibbs", M=4, T=40, warmup=10, n=400,
        seed=0, groundtruth_T=80, combiner=("parametric",),
    )
    uninterrupted = Pipeline(
        spec, checkpoint_dir=tmp_path / "ref", checkpoint_every=15
    ).sample()
    p1 = Pipeline(spec, checkpoint_dir=tmp_path / "run", checkpoint_every=15)
    p1.sample(max_steps=15)
    full = Pipeline(
        spec, checkpoint_dir=tmp_path / "run", checkpoint_every=15
    ).sample()
    assert full.theta.shape == (4, 40, 2)
    assert bool(jnp.all(full.theta == uninterrupted.theta))
    # one T-draw chunk agrees numerically (fusion may differ at the last ulp)
    plain = Pipeline(spec).sample()
    assert bool(jnp.allclose(full.theta, plain.theta, atol=1e-4))
