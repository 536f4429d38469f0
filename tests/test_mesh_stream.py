"""Mesh streaming + mesh fan-out parity, under a forced 4-device host.

The device count is fixed at JAX init, so everything here runs in ONE
subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` and
reports a JSON scorecard that the test functions assert on (a
module-scoped fixture — the subprocess compiles once for all tests).

What the scorecard pins, per ISSUE 7:

- **chunked mesh = chunked vmap, bitwise** for a non-adaptive sampler
  (poisson/gibbs): the backend refactor must not change a single draw.
  Adaptive MH samplers (mala/rwmh) are *not* bitwise across backends —
  ulp-level XLA fusion differences flip accept decisions and amplify
  through the chain — so the cross-backend contract there is statistical,
  not exact; the non-adaptive case is where bitwise is meaningful.
- **stream_combine finals match across backends**: bitwise for buffered
  combiners (the state is the draws themselves), small documented
  tolerance (1e-5) for the moments-backed ``online`` face.
- **every mesh chunk program passes the HLO collective-free assert**
  (``collectives_checked is not None`` — the assert ran; the count may be
  0 when the program legitimately contains no collectives at all).
- **checkpoint/resume works on the mesh** and is bitwise vs an
  uninterrupted mesh run (saves land host-side, restores re-commit to the
  mesh), reporting ``shard_map[resumable](4 devices)``.
- **run_matrix(backend="mesh_fanout")** executes 8 independent cells over
  mesh slices through ONE fanned-out program and reproduces the vmap
  sweep's scoreboard bitwise.
- **the one-call stage on the mesh is the chunk driver**:
  ``sample_subposteriors`` reports ``shard_map[chunked](4 devices)``, has
  asserted both its setup and its chunk program, and a second call with
  the same statics obtains no executable.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import dataclasses, json
    import jax
    import numpy as np

    from repro.api import Pipeline, RunSpec
    from repro.api.matrix import run_matrix

    out = {"device_count": jax.device_count()}

    base = dict(model="poisson", sampler="gibbs",
                combiner=("parametric", "online"), M=4, T=60, warmup=0,
                n=512, seed=0, groundtruth_T=120, stream_every=20,
                score_metric="logl2")
    # (1, 1) normalizes to the vmap backend — Pipeline would otherwise
    # auto-mesh a mesh_shape=None spec on this forced-4-device host
    spec_v = RunSpec(**base, mesh_shape=(1, 1))
    spec_m = RunSpec(**base, mesh_shape=(4, 1))

    # -- chunked draw parity (subscriber path on both backends) ----------
    pv, pm = Pipeline(spec_v), Pipeline(spec_m)
    rv = pv.stream_combine(fused=False)
    rm = pm.stream_combine(fused=False)
    tv = np.asarray(jax.device_get(pv._draws.theta))
    tm = np.asarray(jax.device_get(pm._draws.theta))
    out["theta_bitwise"] = bool((tv == tm).all())
    out["vmap_backend"] = pv._draws.backend
    out["mesh_backend"] = pm._draws.backend
    out["mesh_collectives_checked"] = pm._draws.collectives_checked

    sv = np.asarray(rv.combined["parametric"].samples)
    sm = np.asarray(rm.combined["parametric"].samples)
    out["buffered_final_bitwise"] = bool((sv == sm).all())
    ov = np.asarray(rv.combined["online"].samples)
    om = np.asarray(rm.combined["online"].samples)
    out["online_final_maxabs"] = float(np.abs(ov - om).max())
    out["trajectory_len"] = len(rv.trajectory)
    out["trajectory_equal"] = bool(
        len(rv.trajectory) == len(rm.trajectory) and all(
            a["t"] == b["t"] and a["combiner"] == b["combiner"]
            and a["error"] == b["error"]
            for a, b in zip(rv.trajectory, rm.trajectory)
        )
    )
    # score() reuses the streamed finals -> the fixed-seed scoreboard
    # parity the backends refactor must preserve
    sbv, sbm = pv.score(), pm.score()
    out["stream_board_errors_equal"] = {
        k: bool(sbv.errors[k] == sbm.errors[k]) for k in sbv.errors
    }

    # -- fused mesh hot path vs fused vmap -------------------------------
    bv = Pipeline(spec_v).run()
    bm = Pipeline(spec_m).run()
    out["board_vmap_backend"] = bv.backend
    out["board_mesh_backend"] = bm.backend
    out["board_mesh_collectives_checked"] = bm.collectives_checked
    out["board_errors"] = {"vmap": dict(bv.errors), "mesh": dict(bm.errors)}

    # -- checkpoint/resume on the mesh -----------------------------------
    import tempfile
    with tempfile.TemporaryDirectory() as d1, \\
            tempfile.TemporaryDirectory() as d2:
        p = Pipeline(spec_m, checkpoint_dir=d1, checkpoint_every=20)
        partial = p.sample(max_steps=40)
        out["resume_partial_t"] = partial.t_done
        p2 = Pipeline(spec_m, checkpoint_dir=d1, checkpoint_every=20)
        resumed = p2.sample()
        straight = Pipeline(
            spec_m, checkpoint_dir=d2, checkpoint_every=20
        ).sample()
        tr = np.asarray(jax.device_get(resumed.theta))
        ts = np.asarray(jax.device_get(straight.theta))
        out["resume_bitwise"] = bool((tr == ts).all())
        out["resume_backend"] = resumed.backend

    # -- run_matrix mesh fan-out: 8 cells, one fanned program ------------
    cells = [RunSpec(model="linear", sampler="mala", combiner="parametric",
                     M=4, T=100, warmup=20, n=512, seed=s,
                     groundtruth_T=200, score_metric="logl2")
             for s in range(8)]
    res_v = run_matrix(cells)
    res_f = run_matrix(cells, backend="mesh_fanout")
    out["fanout_backend"] = res_f.backend
    out["fanout_executables"] = res_f.n_executables
    out["fanout_rows_equal"] = all(
        a["error"] == b["error"] and a["accept"] == b["accept"]
        for a, b in zip(res_v.rows, res_f.rows)
    )
    out["fanout_n_rows"] = len(res_f.rows)

    # -- the one-call stage on the mesh: the chunk driver, cached ---------
    from repro.api import get_chunk_backend, sample_subposteriors
    from repro.core.subposterior import partition_data
    from repro.models.bayes import get_model
    from repro.utils import spans

    model = get_model("poisson")
    data, _ = model.generate_data(jax.random.PRNGKey(0), 512)
    kw = dict(sampler="gibbs", warmup=0, burn_in=5, step_size=0.1)
    calls = [
        sample_subposteriors(jax.random.PRNGKey(s), model, data, 4, 20, **kw)
        for s in (1, 2)
    ]
    stage = [r for r in spans.records() if r.name == "sample.stage"][-1]
    shards, _ = partition_data(data, 4, only=model.shard_keys, pad=True)
    be = get_chunk_backend(model, 4, "gibbs", use_counts=False, shards=shards,
                           mesh_shape=(4, 1), **{k: v for k, v in kw.items()
                                                 if k != "sampler"})
    out["oneshot_backends"] = [r.backend for r in calls]
    out["oneshot_collectives_checked"] = calls[-1].collectives_checked
    out["oneshot_theta_shape"] = list(calls[-1].theta.shape)
    out["oneshot_programs_checked"] = sorted({k[0] for k in be._checked})
    out["oneshot_second_executables"] = stage.counters.get("executables", 0)

    print("SCORECARD=" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def scorecard():
    import repro

    src_dir = os.path.dirname(os.path.dirname(os.path.abspath(repro.__file__)))
    env = dict(
        os.environ,
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        JAX_PLATFORMS="cpu",
        PYTHONPATH=src_dir + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, env=env, timeout=1200,
    )
    assert proc.returncode == 0, (
        f"mesh subprocess failed:\n{proc.stdout[-2000:]}\n{proc.stderr[-4000:]}"
    )
    line = [
        ln for ln in proc.stdout.splitlines() if ln.startswith("SCORECARD=")
    ][-1]
    return json.loads(line[len("SCORECARD="):])


def test_subprocess_saw_four_devices(scorecard):
    assert scorecard["device_count"] == 4


def test_chunked_mesh_draws_bitwise_equal_vmap(scorecard):
    assert scorecard["theta_bitwise"] is True
    assert scorecard["vmap_backend"] == "vmap[chunked]"
    assert scorecard["mesh_backend"] == "shard_map[chunked](4 devices)"


def test_mesh_chunk_programs_pass_the_hlo_assert(scorecard):
    # not-None == the per-chunk compiled-HLO assert actually ran (a count
    # of 0 means the program contains no collectives at all — stronger)
    assert scorecard["mesh_collectives_checked"] is not None
    assert scorecard["board_mesh_collectives_checked"] is not None


def test_stream_combine_finals_match_across_backends(scorecard):
    assert scorecard["buffered_final_bitwise"] is True  # draws-backed state
    assert scorecard["online_final_maxabs"] < 1e-5  # moments tolerance
    assert scorecard["trajectory_len"] > 0
    assert scorecard["trajectory_equal"] is True


def test_fixed_seed_scoreboard_parity_across_backends(scorecard):
    # the acceptance contract: same spec, same seed -> same scoreboard,
    # whichever backend sampled (chunk values are bitwise and emitted
    # chunks are localized off the mesh before any combiner computes)
    assert scorecard["stream_board_errors_equal"], "no combiners scored"
    for name, eq in scorecard["stream_board_errors_equal"].items():
        assert eq, f"scoreboard error for {name!r} differs across backends"


def test_fused_mesh_board_is_scored_and_collective_free(scorecard):
    assert scorecard["board_vmap_backend"] == "vmap[fused]"
    assert scorecard["board_mesh_backend"] == "shard_map[fused](4 devices)"
    # fused programs are DIFFERENT executables per backend (AOT shard_map
    # scan vs vmap scan) — gibbs' rejection sampling amplifies their
    # ulp-level divergence into genuinely different (equally valid) draw
    # sequences, so the fused boards are finite and same-shaped, not
    # bitwise; the bitwise scoreboard contract lives on the chunked path
    ev = scorecard["board_errors"]["vmap"]
    em = scorecard["board_errors"]["mesh"]
    assert set(ev) == set(em) and ev, "combiner sets differ or empty"
    import math

    for name in ev:
        assert math.isfinite(ev[name]) and math.isfinite(em[name])
        # empirically ~1e-7 relative on this spec; 1e-2 leaves slack for
        # XLA version drift while still catching a genuinely wrong board
        assert abs(ev[name] - em[name]) <= 1e-2 * max(1.0, abs(ev[name]))


def test_mesh_checkpoint_resume_bitwise(scorecard):
    assert scorecard["resume_partial_t"] == 40
    assert scorecard["resume_bitwise"] is True
    assert scorecard["resume_backend"] == "shard_map[resumable](4 devices)"


def test_one_call_mesh_stage_asserts_setup_and_chunk_programs(scorecard):
    assert scorecard["oneshot_backends"] == ["shard_map[chunked](4 devices)"] * 2
    assert scorecard["oneshot_collectives_checked"] is not None
    assert scorecard["oneshot_theta_shape"] == [4, 20, 2]
    assert scorecard["oneshot_programs_checked"] == ["chunk", "setup"]


def test_second_one_call_mesh_stage_obtains_no_executable(scorecard):
    assert scorecard["oneshot_second_executables"] == 0


def test_mesh_fanout_matrix_reproduces_vmap_sweep(scorecard):
    assert scorecard["fanout_backend"] == "shard_map[fanout](4 devices)"
    assert scorecard["fanout_executables"] == 1  # 8 cells, one program
    assert scorecard["fanout_n_rows"] == 8
    assert scorecard["fanout_rows_equal"] is True
