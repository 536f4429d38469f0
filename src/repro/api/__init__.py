"""repro.api — the programmatic experiment layer over the three registries.

The paper's pipeline is one fixed dataflow (partition data → sample
subposteriors independently → combine → score); this package makes any
model × sampler × combiner × mesh scenario a *value* instead of a script:

- :class:`RunSpec` — a declarative, hashable, JSON-round-trippable spec
  validated against the model/sampler/combiner registries, with a canonical
  ``spec_id`` content hash and a compile-grouping ``executable_signature``;
- :class:`Pipeline` — staged execution (``partition() → sample() →
  combine() → score()``) with explicit typed artifacts (:class:`ShardedData`,
  :class:`SubposteriorDraws`, ``CombineResult``, :class:`Scoreboard`);
  given a ``checkpoint_dir`` the sampling stage persists live kernel state
  via :mod:`repro.checkpoint` and resumes mid-chain, bitwise;
- :func:`run_matrix` — a scenario sweep that compiles one executable per
  distinct signature (seeds/step sizes are runtime inputs) and emits a tidy
  results table (stdout + JSON);
- :func:`combine_draws` — registry-dispatched combination for callers that
  already hold an ``(M, T, d)`` stack (the combine stage's own dispatch).

Quickstart::

    from repro.api import Pipeline, RunSpec

    spec = RunSpec(model="poisson", sampler="rwmh", M=8, T=1000, seed=0)
    board = Pipeline(spec).run()
    print(board.table())

``repro.launch.mcmc_run`` is a thin argparse adapter over this layer;
``examples/`` and ``benchmarks/`` drive it programmatically.
"""

from repro.api.backends import (  # noqa: F401
    BackendId,
    ChunkBackend,
    MeshChunkBackend,
    VmapChunkBackend,
    get_chunk_backend,
)
from repro.api.pipeline import (  # noqa: F401
    LOG_L2_DIM,
    Pipeline,
    Scoreboard,
    ShardedData,
    StreamResult,
    StreamSetup,
    SubposteriorDraws,
    combine_draws,
)
from repro.api.streaming import (  # noqa: F401
    ShardChainStream,
    StreamChunk,
    StreamedSample,
    stream_sample,
)
from repro.api.sampling import (  # noqa: F401
    SampleResult,
    ShardKernel,
    groundtruth_chain,
    make_shard_kernel,
    sample_subposteriors,
)
from repro.api.spec import RunSpec  # noqa: F401


def __getattr__(name: str):
    # lazy: `python -m repro.api.matrix` first imports this package, and an
    # eager submodule import here would re-execute matrix.py as __main__
    # (sys.modules RuntimeWarning, two distinct class identities)
    if name in ("MatrixResult", "run_matrix", "ExecutableCache"):
        from repro.api import matrix

        return getattr(matrix, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "BackendId",
    "ChunkBackend",
    "LOG_L2_DIM",
    "MatrixResult",
    "MeshChunkBackend",
    "Pipeline",
    "VmapChunkBackend",
    "get_chunk_backend",
    "RunSpec",
    "SampleResult",
    "Scoreboard",
    "ShardChainStream",
    "ShardKernel",
    "ShardedData",
    "StreamChunk",
    "StreamResult",
    "StreamSetup",
    "StreamedSample",
    "SubposteriorDraws",
    "combine_draws",
    "groundtruth_chain",
    "make_shard_kernel",
    "run_matrix",
    "sample_subposteriors",
    "stream_sample",
]
