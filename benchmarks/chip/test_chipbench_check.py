"""The check that decides ``correct``: its numbers, its control, its faults.

The control and the faults run a whole cell on the CPU, with the harness's
look for a chip skipped: ``logreg-paper.batch-semiparametric`` at its own
size, and the covertype cells' traffic on the covertype design cut to a
twelfth of its rows, against those cells' limits. A sound run asks for
two jobs in its window; a broken one gets one. A run keeps of each job only
its summary, made as the job ends with the window's clock stopped, and
samples the model that its model file builds, where it builds one.
Each fault breaks the timed path underneath the harness, in the program,
and the run has to come out not correct. The check belongs to the model
file: through it the logistic-regression cells read what the harness read
before, and a linear-Gaussian configuration that lives in these tests
alone is judged by numbers of its own.
"""

from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench import cell, check, harness  # noqa: E402
from chipbench.jobs import Jobs, seed_key  # noqa: E402

SEED = 2**33 + 12345


def _ref(d=3, m=2, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.normal(size=(m, d, d))
    sub_cov = a @ a.transpose(0, 2, 1) + d * np.eye(d)
    full_cov = np.linalg.inv(np.linalg.inv(sub_cov).sum(0))
    return {"sub_mean": rng.normal(size=(m, d)), "sub_cov": sub_cov,
            "full_mean": rng.normal(size=d), "full_cov": full_cov}


def _exact(mean, cov, t, rng):
    """Draws whose sample mean and covariance are exactly ``mean``, ``cov``."""
    z = rng.normal(size=(t, mean.shape[-1]))
    z -= z.mean(0)
    z = z @ np.linalg.inv(np.linalg.cholesky(np.cov(z.T)).T)
    return mean + z @ np.linalg.cholesky(cov).T


def test_numbers_are_zero_on_exact_moments():
    ref, rng = _ref(), np.random.default_rng(1)
    sub = np.stack([_exact(ref["sub_mean"][i], ref["sub_cov"][i], 500, rng) for i in range(2)])
    comb = _exact(ref["full_mean"], ref["full_cov"], 500, rng)
    got = check.numbers(check.moments(sub, comb), ref)
    assert max(got.values()) < 1e-9
    window = check.window_numbers([check.moments(sub, comb)] * 3, ref)
    assert max(window.values()) < 1e-9


def test_numbers_by_hand():
    ref, rng = _ref(), np.random.default_rng(2)
    sd = np.sqrt(np.diagonal(ref["sub_cov"], axis1=1, axis2=2))
    sub = np.stack([_exact(ref["sub_mean"][i], ref["sub_cov"][i], 500, rng) for i in range(2)])
    sub[1] += 0.5 * sd[1]  # shard 1 off by half an sd in every coordinate
    sub[0] = ref["sub_mean"][0] + 2.0 * (sub[0] - ref["sub_mean"][0])  # sd doubled
    comb = _exact(ref["full_mean"], ref["full_cov"], 500, rng)
    got = check.numbers(check.moments(sub, comb), ref)
    assert got["sub_mean"] == pytest.approx(0.5)
    assert got["sub_sd"] == pytest.approx(np.log(2.0))
    assert got["comb_mean"] < 1e-9 and got["comb_sd"] < 1e-9


def test_a_chain_that_never_moves_fails_every_limit():
    ref, rng = _ref(), np.random.default_rng(3)
    sub = np.stack([_exact(ref["sub_mean"][i], ref["sub_cov"][i], 100, rng) for i in range(2)])
    sub[0] = sub[0, :1]  # the first draw, repeated
    comb = _exact(ref["full_mean"], ref["full_cov"], 100, rng)
    got = check.numbers(check.moments(sub, comb), ref)
    assert got["sub_sd"] > 20  # sd at rounding level: log ratio ~ -35
    assert not check.judge(got, {k: 1.0 for k in check.NAMES})


def test_the_window_averages_job_means_and_reads_the_worst_job():
    ref, rng = _ref(), np.random.default_rng(4)
    sd = np.sqrt(np.diagonal(ref["sub_cov"], axis1=1, axis2=2))
    ms = []
    for shift in (+0.4, -0.4, +0.1):  # per-job mean errors, in sds
        sub = np.stack([_exact(ref["sub_mean"][i], ref["sub_cov"][i], 300, rng)
                        for i in range(2)]) + shift * sd[:, None, :]
        ms.append(check.moments(sub, _exact(ref["full_mean"], ref["full_cov"], 300, rng)))
    per_job, worst = check.readings(ms, ref)
    assert [r["sub_mean"] for r in per_job] == pytest.approx([0.4, 0.4, 0.1])
    assert worst["sub_mean"] == pytest.approx(0.4)
    assert worst["sub_mean_window"] == pytest.approx(0.1 / 3)
    assert worst["comb_mean_window"] < 1e-9


# -- whole runs on the CPU ----------------------------------------------------

LOGREG = "logreg-paper.batch-semiparametric"
COVTYPE = "covtype-paper.batch-parametric"


def _cell(workload):
    c = cell.find(workload)
    if c.config["name"] == "covtype-paper":
        # a twelfth of the rows keeps a CPU job to seconds; the shards keep
        # the configuration's M, so each holds ~1,000 rows
        c = c._replace(config={**c.config, "N": c.config["N"] // 12})
    return c._replace(chips=1)


def _run(c, seconds=0.0, min_jobs=1):
    return harness.execute(c, SEED, seconds, False, jax.devices()[:1], time.perf_counter(),
                           min_jobs=min_jobs)


@pytest.fixture
def fresh_programs(monkeypatch):
    """Compiled sampling programs are cached per process; a fault that
    patches what they are built from needs them built anew."""
    from repro.api import backends, streaming

    monkeypatch.setattr(backends, "_BACKEND_CACHE", {})
    monkeypatch.setattr(streaming, "_FUSED_SAMPLE_CACHE", {})
    return monkeypatch


def _frozen_step(mp):
    from repro.samplers import registry
    from repro.samplers.base import MCMCKernel

    real = registry.mala_kernel

    def frozen(logpdf, step_size=0.05):
        k = real(logpdf, step_size=step_size)
        return MCMCKernel(k.init, lambda key, state: (state, k.step(key, state)[1]))

    mp.setattr(registry, "mala_kernel", frozen)


def _half_batch(mp):
    from repro.api import sampling

    real = sampling.make_subposterior_logpdf

    def half(log_prior, log_lik, shard, num_shards, *, count=None, per_datum=None):
        n = shard["x"].shape[0] // 2
        kept = {k: (v[:n] if per_datum is None or k in per_datum else v)
                for k, v in shard.items()}
        return real(log_prior, lambda th, d: 2.0 * log_lik(th, d), kept,
                    num_shards, count=None, per_datum=per_datum)

    mp.setattr(sampling, "make_subposterior_logpdf", half)


def _answer_altered(mp):
    from repro.api import backends

    real = backends._chunk_one

    def altered(sk, shard, count, eps, state, keys):
        state, theta, acc = real(sk, shard, count, eps, state, keys)
        return state, theta.at[..., 0].set(0.0), acc  # a lost write

    mp.setattr(backends, "_chunk_one", altered)


FAULTS = {
    "frozen_step": (_frozen_step, [LOGREG, COVTYPE]),
    "half_batch": (_half_batch, [LOGREG, COVTYPE]),
    "answer_altered": (_answer_altered, [LOGREG, COVTYPE]),
}
CASES = [(f, w) for f, (_, ws) in FAULTS.items() for w in ws]


def _per_job(limits):
    return {k: v for k, v in limits.items() if k in check.JOB_NAMES}


def test_a_sound_run_is_correct():
    # two jobs, however fast the machine, so the window's average sheds some
    # Monte Carlo error
    result = _run(_cell(LOGREG), min_jobs=2)
    assert result["correct"], result["check"]
    assert result["attempted"] >= 2 and result["failed"] == 0


def test_a_sound_run_on_a_twelfth_of_the_rows_passes_the_per_job_limits():
    # the window's limits are set at the cell's own size: on ~1,000-row
    # shards the parametric product's own bias is larger than on 12,105
    c = _cell(COVTYPE)
    result = _run(c, seconds=8.0)
    reading = {k: v["value"] for k, v in result["check"].items()}
    assert check.judge(reading, _per_job(c.limits)), result["check"]


@pytest.mark.parametrize("fault,workload", CASES)
def test_a_broken_timed_path_is_not_correct(fault, workload, fresh_programs):
    FAULTS[fault][0](fresh_programs)
    result = _run(_cell(workload))
    assert not result["correct"], result["check"]


@pytest.mark.parametrize("workload", [LOGREG, COVTYPE])
def test_the_control_is_not_correct_and_its_float32_witness_is(workload):
    c = _cell(workload)
    m, cfg = c.model, c.config
    key = seed_key(SEED)
    data = m.make_data(jax.random.fold_in(key, 0), cfg)
    ref = m.reference(data, cfg)
    readings = {}
    for dtype in (jnp.bfloat16, jnp.float32):
        job = m.control(jax.random.fold_in(key, 1), data, cfg, dtype)
        readings[dtype] = m.readings([m.summarize(job, cfg)], ref, cfg)[1]
    assert not check.judge(readings[jnp.bfloat16], c.limits), readings
    # one job of the plain sampler in float32 is within every per-job limit
    assert check.judge(readings[jnp.float32], _per_job(c.limits)), readings


# -- the model file's contract ------------------------------------------------


def _kept_outputs(monkeypatch):
    """The window's job outputs, kept as they end (the warm-up job's not)."""
    outputs = []
    run = Jobs.run

    def kept(self, j):
        out = run(self, j)
        if j > 0:
            outputs.append(out)
        return out

    monkeypatch.setattr(Jobs, "run", kept)
    return outputs


@pytest.mark.parametrize("fault", [None, "half_batch"])
def test_the_contract_reads_what_the_draw_moments_read(fault, fresh_programs):
    """The harness's check through the model file equals the comparison the
    harness made itself before the check moved there: ``check.moments`` and
    ``check.readings`` on ``laplace``, for the same outputs, of a sound and
    of a broken sampler (at a size where the cell's limits need not hold)."""
    if fault:
        FAULTS[fault][0](fresh_programs)
    c = _cell(LOGREG)
    cfg = dict(c.config, N=2000, M=2, T=300, warmup=50, burn_in=50)
    c = c._replace(config=cfg)
    outputs = _kept_outputs(fresh_programs)
    result = _run(c, seconds=0.5)

    data = c.model.make_data(jax.random.fold_in(seed_key(SEED), 0), cfg)
    ref = c.model.laplace(np.asarray(data["x"]), np.asarray(data["y"]), cfg)
    ms = [check.moments(np.asarray(o.sample.theta),
                        np.asarray(o.combine["semiparametric"].samples)) for o in outputs]
    per_job, worst = check.readings(ms, ref)
    failed = sum(not check.judge(r, c.limits) for r in per_job)
    if not check.judge({k: worst[k] for k in check.WINDOW_NAMES}, c.limits):
        failed = len(outputs)
    assert result["check"] == {
        k: {"value": worst[k], "limit": float(v)} for k, v in c.limits.items()}
    assert result["attempted"] == len(outputs) >= 1
    assert result["failed"] == failed
    assert result["correct"] == (failed == 0)


LINEAR_LIMITS = {"shard_z": 0.6, "shard_log_sd": 0.3, "product_z": 1.0,
                 "product_log_sd": 0.25, "shard_z_mean": 0.4}


def _linear_cell(tmp_path, monkeypatch, **changes):
    """A configuration of the program's linear_gaussian model that lives in
    this test alone: its model file (``testdata/linear_gaussian.py``, exact
    conjugate reference, numbers of its own), traffic and limits, beside the
    benchmark's metric readers; ``changes`` go into the configuration."""
    for part in ("configs", "traffic", "limits"):
        (tmp_path / part).mkdir()
    shutil.copytree(HERE / "metrics", tmp_path / "metrics")
    shutil.copy(HERE / "testdata" / "linear_gaussian.py", tmp_path / "configs")
    config = {"name": "linear", "model": "linear_gaussian",
              "model_file": "linear_gaussian.py", "N": 4000, "d": 10, "M": 4,
              "sampler": "mala", "warmup": 200, "T": 1000, "burn_in": 100,
              "step_size": 0.1, **changes}
    files = {
        "configs/linear.json": config,
        "traffic/batch.json": {"loop": "closed", "combiner": "parametric",
                               "stream_every": 0, "trace_jobs": 1},
        "limits/linear.batch.json": LINEAR_LIMITS,
    }
    for name, body in files.items():
        (tmp_path / name).write_text(json.dumps(body))
    bench = {"configs": [{"name": "linear", "file": str(tmp_path / "configs/linear.json")}],
             "workloads": [{"name": "linear.batch", "config": "linear",
                            "traffic": "batch", "chips": 1}],
             "end_to_end": [{"name": "job_s", "unit": "s"}, {"name": "setup_s", "unit": "s"}],
             "per_layer": []}
    monkeypatch.setattr(cell, "BENCH_DIR", tmp_path)
    return cell.find("linear.batch", bench)


@pytest.mark.parametrize("fault", [None, "half_batch"])
def test_a_configuration_with_a_check_of_its_own(fault, tmp_path, fresh_programs):
    c = _linear_cell(tmp_path, fresh_programs)
    assert not set(c.model.NUMBERS) & set(check.NAMES)
    if fault:
        FAULTS[fault][0](fresh_programs)
    result = _run(c, seconds=0.5 if fault is None else 0.0)
    assert set(result["check"]) == set(LINEAR_LIMITS)
    assert result["correct"] == (fault is None), result["check"]
    assert result["failed"] == (0 if fault is None else result["attempted"])


# -- each job summarized as it ends -------------------------------------------


def test_the_device_holds_one_job_at_a_time(tmp_path, fresh_programs):
    """The live device arrays, and their bytes, are the same at every window
    job's start: a job's output is dropped once it is summarized."""
    c = _linear_cell(tmp_path, fresh_programs)
    live = []
    run = Jobs.run

    def counted(self, j):
        arrays = jax.live_arrays()
        live.append((len(arrays), sum(a.nbytes for a in arrays)))
        return run(self, j)

    fresh_programs.setattr(Jobs, "run", counted)
    result = _run(c, min_jobs=6)
    assert result["attempted"] == 6 and len(live) == 7
    assert len(set(live[1:])) == 1, live


def test_summaries_stop_neither_clock(tmp_path, fresh_programs):
    """A summary that takes 0.5 s moves neither ``job_s`` nor ``setup_s``."""
    c = _linear_cell(tmp_path, fresh_programs, N=2000, T=200, warmup=50, burn_in=50)
    starts = []
    summarize = c.model.summarize

    def slow(output, cfg):
        starts.append(time.perf_counter())
        time.sleep(0.5)
        return summarize(output, cfg)

    fresh_programs.setattr(c.model, "summarize", slow)
    t_start = time.perf_counter()
    result = harness.execute(c, SEED, 0.0, False, jax.devices()[:1], t_start, min_jobs=4)
    metric = {k: v["value"] for k, v in result["metrics"].items()}
    jobs = result["attempted"]
    assert jobs == 4 and len(starts) == 5  # the warm-up job's summary first
    assert metric["job_s"] * jobs < jobs * 0.5  # window_s < jobs x 0.5 s
    assert t_start + metric["setup_s"] <= starts[0]


def test_a_traced_run_summarizes_its_traced_jobs_after_the_profiler(
        tmp_path, fresh_programs):
    """The traced jobs are judged with the window's, and no summary runs
    while the profiler records."""
    c = _linear_cell(tmp_path, fresh_programs)
    c = c._replace(per_layer=[{"name": "sample_s", "unit": "s"},
                              {"name": "combine_s", "unit": "s"}])
    profiling, calls = [False], []
    for name, on in (("start_trace", True), ("stop_trace", False)):
        real = getattr(jax.profiler, name)

        def switch(*args, real=real, on=on, **kwargs):
            real(*args, **kwargs)
            profiling[0] = on

        fresh_programs.setattr(jax.profiler, name, switch)
    summarize = c.model.summarize

    def watched(output, cfg):
        calls.append(profiling[0])
        return summarize(output, cfg)

    fresh_programs.setattr(c.model, "summarize", watched)
    result = harness.execute(c, SEED, 0.0, True, jax.devices()[:1], time.perf_counter())
    assert result["correct"], result["check"]
    assert result["attempted"] == 1 + c.traffic["trace_jobs"]
    assert set(result["metrics"]) == {"sample_s", "combine_s"}
    assert calls == [False] * (1 + result["attempted"])  # the warm-up job's too


def test_summaries_made_as_jobs_end_read_as_after_the_window(tmp_path, fresh_programs):
    """Each job summarized as it ends gives the ``check`` that the same jobs'
    outputs, kept and summarized after the window, give."""
    c = _linear_cell(tmp_path, fresh_programs)
    outputs = _kept_outputs(fresh_programs)
    result = _run(c, min_jobs=3)
    m, cfg = c.model, c.config
    ref = m.reference(m.make_data(jax.random.fold_in(seed_key(SEED), 0), cfg), cfg)
    per_job, window = m.readings([m.summarize(o, cfg) for o in outputs], ref, cfg)
    assert result["attempted"] == len(outputs) == len(per_job) == 3
    assert result["check"] == {
        k: {"value": window[k], "limit": float(v)} for k, v in c.limits.items()}


def test_the_model_file_builds_the_model_the_jobs_sample(tmp_path, fresh_programs):
    """A prior scale stated by the configuration reaches the sampled model
    through the model file's ``bayes_model``: the draws follow it, and the
    registered model, with its default prior, fails the same reference."""
    from repro.models.bayes import get_model, linear_gaussian

    c = _linear_cell(tmp_path, fresh_programs, prior_sigma=0.05)
    key = seed_key(SEED)
    data = c.model.make_data(jax.random.fold_in(key, 0), c.config)
    theta = jnp.full((10,), 0.1)
    sampled = Jobs(c, data, key).model
    assert float(sampled.log_prior(theta)) == pytest.approx(
        float(linear_gaussian.log_prior(theta, tau=0.05)))
    result = _run(c)
    assert result["correct"], result["check"]

    fresh_programs.delattr(c.model, "bayes_model")
    assert Jobs(c, data, key).model is get_model("linear_gaussian")
    result = _run(c)
    assert not result["correct"], result["check"]
