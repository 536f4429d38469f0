"""repro.serve — posterior-as-a-service on the streaming combine engine.

The paper's machines sample independently and communicate only at
combination time (§3/§4); PRs 5–8 built the engine that *folds* chunks as
they land. This package is the layer that **serves** the evolving posterior
while the chains still extend — the north-star "heavy traffic from millions
of users" shape of ROADMAP item 1:

- :class:`~repro.serve.state.ServeState` — the deterministic core: folds
  :class:`~repro.api.streaming.StreamChunk` events through the same
  :class:`~repro.api.pipeline.StreamSetup` surfaces ``stream_combine``
  uses, refreshes cheap per-combiner estimates with the trajectory RNG
  discipline (bitwise ``stream_combine``'s rows), and owns the staleness
  counters every response carries;
- :mod:`~repro.serve.handlers` — the pure query surface (``mean_cov``,
  ``quantiles``, ``draws``, ``logpdf`` via the PR-8 batched machine-KDE
  scorer, ``status``), typed 503s for combiners that cannot estimate
  (:class:`~repro.core.combiners.api.EstimateUnavailable`);
- :class:`~repro.serve.server.PosteriorServer` — the asyncio loop: sampler
  in an executor thread feeding a bounded chunk queue, a folder task that
  never drops chunks but coalesces estimate refreshes under backpressure,
  and TCP/in-process readers answering from the freshest snapshot;
- :class:`~repro.serve.client.ServeClient` — the matching
  newline-delimited-JSON client.

Readers consume *stale* combine state without a barrier — principled per
Terenin et al.'s Asynchronous Gibbs analysis — so every response reports
``chunks_folded`` / ``draws_seen`` / ``last_fold_monotonic_s`` / ``spec_id``.
Restart degrades gracefully to the last checkpoint: build the Pipeline with
its ``checkpoint_dir`` and the server rebuilds state from replayed
(``replayed=True``) chunks without double-counting.

Quickstart (also ``python -m repro.launch.mcmc_run ... --serve``)::

    from repro.api import Pipeline, RunSpec
    from repro.serve import serve_pipeline

    spec = RunSpec(model="linear", sampler="mala", M=4, T=2000,
                   stream_every=100, combiner=("parametric", "online"))
    serve_pipeline(Pipeline(spec), probe_readers=8)

Not to be confused with :mod:`repro.launch.serve`, the LM prefill/decode
driver — this package serves *posteriors*, not tokens.
"""

from repro.serve.client import ServeClient, ServeError  # noqa: F401
from repro.serve.handlers import HANDLERS, BadRequest, answer  # noqa: F401
from repro.serve.server import PosteriorServer, serve_pipeline  # noqa: F401
from repro.serve.state import EstimateSnapshot, ServeState  # noqa: F401

__all__ = [
    "EstimateSnapshot",
    "HANDLERS",
    "PosteriorServer",
    "ServeClient",
    "ServeError",
    "ServeState",
    "answer",
    "serve_pipeline",
]
