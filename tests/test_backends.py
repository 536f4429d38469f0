"""repro.api.backends: BackendId strings, backend resolution, caching.

The BackendId spellings are load-bearing — ``Scoreboard.backend`` strings
are pinned by tests across the repo (``"vmap[resumable]"``,
``"shard_map[chunked](4 devices)"``, …) and this module is their single
constructor. Everything here runs single-device; the mesh backend's
*execution* is covered by tests/test_mesh_stream.py under a forced
multi-device subprocess.
"""

import jax
import pytest

from repro.api import BackendId, get_chunk_backend
from repro.api.backends import CHUNKED, FUSED, RESUMABLE, VmapChunkBackend
from repro.core.subposterior import partition_data
from repro.models.bayes import get_model


# ---------------------------------------------------------------------------
# BackendId — the exact strings every backend reports
# ---------------------------------------------------------------------------


def test_backend_id_vmap_spellings():
    assert BackendId.vmap() == "vmap"
    assert BackendId.vmap(CHUNKED) == "vmap[chunked]"
    assert BackendId.vmap(FUSED) == "vmap[fused]"
    assert BackendId.vmap(RESUMABLE) == "vmap[resumable]"


def test_backend_id_mesh_spellings():
    # every mesh stage runs the chunk driver, so a mesh id names its mode
    assert BackendId.mesh(4, CHUNKED) == "shard_map[chunked](4 devices)"
    assert BackendId.mesh(2, FUSED) == "shard_map[fused](2 devices)"
    assert BackendId.mesh(2, RESUMABLE) == "shard_map[resumable](2 devices)"
    with pytest.raises(ValueError, match="reports its mode"):
        BackendId.mesh(4, None)


def test_backend_id_fanout_and_distributed_spellings():
    assert BackendId.mesh_fanout(4) == "shard_map[fanout](4 devices)"
    assert BackendId.distributed(2) == "jax.distributed(2 processes)"
    assert BackendId.distributed(1) == "jax.distributed(1 processes)"


def test_backend_id_rejects_unknown_modes():
    with pytest.raises(ValueError, match="unknown backend mode"):
        BackendId.vmap("oneshot")
    with pytest.raises(ValueError, match="unknown backend mode"):
        BackendId.mesh(4, "streamed")


# ---------------------------------------------------------------------------
# get_chunk_backend — resolution + caching
# ---------------------------------------------------------------------------


def _stage_inputs(M=4, n=256):
    model = get_model("poisson")
    data, _ = model.generate_data(jax.random.PRNGKey(0), n)
    shards, counts = partition_data(data, M, only=model.shard_keys, pad=True)
    return model, shards, counts


def test_resolves_vmap_backend_and_caches_by_statics():
    model, shards, _ = _stage_inputs()
    kw = dict(warmup=0, burn_in=5, step_size=0.1, sgld_batch=256,
              sampler_options=(), use_counts=True)
    b1 = get_chunk_backend(model, 4, "gibbs", shards=shards, **kw)
    b2 = get_chunk_backend(model, 4, "gibbs", shards=shards, **kw)
    assert isinstance(b1, VmapChunkBackend)
    assert b1 is b2  # same statics -> same cached backend (no re-trace)
    assert b1.backend_id(CHUNKED) == "vmap[chunked]"
    assert b1.collectives_checked is None  # nothing to assert off the mesh
    b3 = get_chunk_backend(model, 4, "gibbs", shards=shards,
                           **{**kw, "burn_in": 6})
    assert b3 is not b1  # any compile-relevant static forks the cache


def test_mesh_shape_of_one_is_the_vmap_backend():
    # a degenerate (1, 1) mesh would be pure overhead — normalize to vmap
    model, shards, _ = _stage_inputs()
    b = get_chunk_backend(
        model, 4, "gibbs", warmup=0, burn_in=5, step_size=0.1,
        sgld_batch=256, sampler_options=(), use_counts=True,
        shards=shards, mesh_shape=(1, 1),
    )
    assert isinstance(b, VmapChunkBackend)


def test_mesh_backend_without_devices_raises_actionably():
    model, shards, _ = _stage_inputs()
    if jax.device_count() >= 4:
        pytest.skip("host exposes enough devices; error path unreachable")
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        get_chunk_backend(
            model, 4, "gibbs", warmup=0, burn_in=5, step_size=0.1,
            sgld_batch=256, sampler_options=(), use_counts=True,
            shards=shards, mesh_shape=(4, 1),
        )


# ---------------------------------------------------------------------------
# the drivers actually report BackendId strings
# ---------------------------------------------------------------------------


def test_pipeline_reports_backend_id_strings(tmp_path):
    from repro.api import Pipeline, RunSpec

    spec = RunSpec(model="poisson", sampler="gibbs", combiner="parametric",
                   M=4, T=40, warmup=0, n=256, groundtruth_T=80,
                   stream_every=20)
    board = Pipeline(spec).run()
    assert board.backend == BackendId.vmap(FUSED)  # streamed + fusable

    board2 = Pipeline(spec, checkpoint_dir=tmp_path).run()
    assert board2.backend == BackendId.vmap(RESUMABLE)
