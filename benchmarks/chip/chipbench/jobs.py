"""What one job is, and the closed loop that issues them.

A job runs the program's sample stage and then its combine stage, from the
partitioned data on the device to finalized combined draws, through the
calls ``repro.api.Pipeline.sample`` and ``Pipeline.combine`` make:
``stream_sample`` (or ``sample_subposteriors`` on a mesh with no stream
cadence), then ``combine_spec_draws``. The partition is made once, in
set-up, by the program's own ``partition_data``. Every job has a sampling
key of its own, derived from the seed and the job's index, with the
pipeline's key discipline (sampling ``fold_in(key, 1)``, combine streams
under ``fold_in(key, 3)``).

What the combine stage takes from the sample stage's result is the
configuration's to say: its model file's ``handoff``. The sample stage's
time ends when that is ready, the combine stage's when the combined draws
are. The model the jobs sample is the model file's too, where it builds one
(``bayes_model``); otherwise the program's registered ``config["model"]``.

A run keeps of each job only the model file's summary of it, made as the job
ends and outside the window's clock, so the device holds one job's output at
a time.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, NamedTuple

import jax
from jax.profiler import TraceAnnotation

from repro.api.pipeline import combine_spec_draws
from repro.api.sampling import sample_subposteriors
from repro.api.spec import RunSpec
from repro.api.streaming import stream_sample
from repro.core.subposterior import partition_data
from repro.models.bayes import get_model

from chipbench.cell import Cell


def seed_key(seed: int) -> jax.Array:
    """A key from the whole seed: both 32-bit halves count."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


class Output(NamedTuple):
    sample: Any  # the sample stage's result, as the program returns it
    combine: Dict[str, Any]  # the combine stage's result: {combiner: CombineResult}
    sample_s: float
    combine_s: float


class Done(NamedTuple):
    """What a run keeps of one job once it has ended."""

    summary: Any  # the model file's summarize(output, cfg)
    sample_s: float
    combine_s: float
    summarize_s: float


class Jobs:
    """The program set up for one cell's configuration and traffic mix."""

    def __init__(self, cell: Cell, data: Dict[str, jax.Array], key: jax.Array):
        config, traffic, chips = cell.config, cell.traffic, cell.chips
        if traffic["loop"] != "closed":
            raise ValueError(f"unknown loop {traffic['loop']!r}")
        self.handoff = cell.model.handoff
        self._summarize = cell.model.summarize
        self.config = config
        build = getattr(cell.model, "bayes_model", None)
        self.model = build(config) if build else get_model(config["model"])
        self.combiner = traffic["combiner"]
        self.spec = RunSpec(
            model=config["model"],
            sampler=config["sampler"],
            combiner=self.combiner,
            M=int(config["M"]),
            T=int(config["T"]),
            warmup=int(config["warmup"]),
            burn_in=int(config["burn_in"]),
            step_size=float(config["step_size"]),
            n=int(config["N"]),
            stream_every=int(traffic["stream_every"]),
            mesh_shape=(chips, 1),
            combiner_options=traffic.get("combiner_options", {}),
            # RunSpec's defaults where the configuration states none
            **{k: config[k] for k in ("sampler_options", "sgld_batch") if k in config},
        ).validate()
        self.use_mesh = chips > 1
        self.data = data
        self.shards, self.counts = partition_data(
            data, self.spec.M, only=self.model.shard_keys, pad=True
        )
        jax.block_until_ready((self.shards, self.counts))
        self.key = key

    def job_key(self, j: int) -> jax.Array:
        return jax.random.fold_in(jax.random.fold_in(self.key, 1), j)

    def sample(self, key: jax.Array):
        """The sample stage, routed as ``Pipeline.sample`` routes it."""
        spec, k = self.spec, jax.random.fold_in(key, 1)
        common = dict(
            sampler=spec.sampler, warmup=spec.warmup,
            burn_in=spec.resolved_burn_in(), step_size=spec.step_size,
            sgld_batch=spec.sgld_batch, sampler_options=spec.sampler_options,
            shards=self.shards, counts=self.counts,
        )
        if self.use_mesh and spec.stream_every == 0:
            res = sample_subposteriors(
                k, self.model, self.data, spec.M, spec.T,
                check_hlo=True, mesh_shape=spec.mesh_shape, **common,
            )
        else:
            res = stream_sample(
                k, self.model, self.data, spec.M, spec.T,
                chunk_size=spec.stream_every,
                mesh_shape=spec.mesh_shape if self.use_mesh else None,
                **common,
            ).result
        jax.block_until_ready(self.handoff(res))
        return res

    def combine(self, key: jax.Array, sample) -> Dict[str, Any]:
        """The combine stage, as ``Pipeline.combine`` runs it."""
        res = combine_spec_draws(self.spec, key, self.handoff(sample), (self.combiner,))
        jax.block_until_ready(res[self.combiner].samples)
        return res

    def run(self, j: int) -> Output:
        """Job ``j``, under the spans the trace attributes device time to."""
        key = self.job_key(j)
        with TraceAnnotation("job"):
            t0 = time.perf_counter()
            with TraceAnnotation("sample"):
                sample = self.sample(key)
            t1 = time.perf_counter()
            with TraceAnnotation("combine"):
                combined = self.combine(key, sample)
            t2 = time.perf_counter()
        return Output(sample, combined, t1 - t0, t2 - t1)

    def summarize(self, output: Output) -> Done:
        """The model file's summary of ``output``, finished on the device."""
        t0 = time.perf_counter()
        summary = jax.block_until_ready(self._summarize(output, self.config))
        return Done(summary, output.sample_s, output.combine_s, time.perf_counter() - t0)

    def loop(self, first: int, seconds: float, min_jobs: int = 1) -> tuple:
        """Jobs back to back from index ``first`` until ``seconds`` of job
        time have passed and ``min_jobs`` have ended: ``(done, window_s)``.

        Each job is summarized as it ends and its output dropped before the
        next job starts. The clock stops while a job is summarized:
        ``window_s`` is the time from the first job's start to the last
        job's end, less the time spent in summaries.
        """
        done: List[Done] = []
        paused = 0.0
        start = time.perf_counter()
        while True:
            output = self.run(first + len(done))
            ended = time.perf_counter()
            done.append(self.summarize(output))
            del output  # its device buffers go before the next job starts
            elapsed = ended - start - paused
            paused += time.perf_counter() - ended
            if elapsed >= seconds and len(done) >= min_jobs:
                return done, elapsed
