"""Pluggable chunk-emitting execution backends for the sampling stage.

Before this module the sampling stage had three forked paths — the chunked
vmap driver, its fused whole-run variant, and a one-shot ``shard_map`` mesh
program — and the forks leaked upward: ``Pipeline.sample`` raised on any
spec that asked for both a mesh and a stream. The fork is now a *backend*:
one :class:`ChunkBackend` contract (jitted ``setup`` + ``next_chunk`` chunk
programs, a ``fused_program`` runner, a backend-id constructor, an HLO
assert hook) with two implementations —

- :class:`VmapChunkBackend` — M chains vmapped on one device, the classic
  driver behind ``"vmap[chunked]"`` / ``"vmap[fused]"`` / ``"vmap[resumable]"``;
- :class:`MeshChunkBackend` — the *same* vmapped per-chain programs wrapped
  in ``shard_map`` over the ``data`` axis of a ``(ndata, nmodel)`` mesh, so
  every chunk is a compiled SPMD program whose post-SPMD HLO is asserted
  collective-free across chains (lazily, once per chunk shape) exactly like
  the historical one-shot path. Chunks land as dense ``(M, C, d)`` device
  slices — the same streaming-gather layout
  :func:`repro.distributed.epmcmc.gather_subset_samples` produces with
  ``chunk=`` — so every chunk subscriber (checkpointing, streaming
  combiners, :func:`repro.api.streaming.fused_fold`) drives either backend
  unchanged.

:class:`BackendId` is the one constructor for ``Scoreboard.backend``
strings; call sites must not assemble them ad hoc. The historical strings
are preserved exactly (``"vmap"``, ``"vmap[chunked]"``,
``"shard_map(4 devices)"``, …); mesh streaming adds the bracketed variants
(``"shard_map[chunked](4 devices)"``) and the multi-controller launch path
(:mod:`repro.api.launch`) adds ``"jax.distributed(2 processes)"``.

Backends are cached per compile-relevant statics (the run_matrix compile-
hygiene convention): a serving loop instantiating one stream per request
re-traces nothing, and the HLO assert runs once per (program, chunk shape)
per process.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Protocol, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.api.sampling import ShardKernel, _shard_axes, chain_mesh, make_shard_kernel
from repro.models.bayes import BayesModel
from repro.samplers.adaptation import warmup_chain

PyTree = Any

# execution modes a chunk backend can report (BackendId bracket tags)
CHUNKED = "chunked"
FUSED = "fused"
RESUMABLE = "resumable"
_MODES = (None, CHUNKED, FUSED, RESUMABLE)


class BackendId:
    """The one constructor for sampling-backend identifier strings.

    ``Scoreboard.backend`` / ``SampleResult.backend`` values are assembled
    here and nowhere else — tests assert call sites against these exact
    spellings, so the historical strings are load-bearing.
    """

    @staticmethod
    def _check_mode(mode: Optional[str]) -> None:
        if mode not in _MODES:
            raise ValueError(
                f"unknown backend mode {mode!r} (choices: "
                f"{', '.join(repr(m) for m in _MODES)})"
            )

    @staticmethod
    def vmap(mode: Optional[str] = None) -> str:
        """``"vmap"`` or ``"vmap[chunked|fused|resumable]"``."""
        BackendId._check_mode(mode)
        return "vmap" if mode is None else f"vmap[{mode}]"

    @staticmethod
    def mesh(ndata: int, mode: Optional[str] = None) -> str:
        """``"shard_map(<ndata> devices)"`` (one-shot) or the bracketed
        chunk-streaming variants; ``ndata`` is the mesh data-axis size —
        the number of chain groups, the historical spelling."""
        BackendId._check_mode(mode)
        tag = "" if mode is None else f"[{mode}]"
        return f"shard_map{tag}({int(ndata)} devices)"

    @staticmethod
    def mesh_fanout(ndev: int) -> str:
        """``run_matrix`` fanning whole cells over a 1-axis device mesh
        (:func:`repro.api.matrix._fanout_sample`)."""
        return f"shard_map[fanout]({int(ndev)} devices)"

    @staticmethod
    def distributed(num_processes: int) -> str:
        """The multi-controller launch path (:mod:`repro.api.launch`)."""
        return f"jax.distributed({int(num_processes)} processes)"


class ChunkBackend(Protocol):
    """What every chunk-emitting execution backend provides.

    The drivers (:class:`repro.api.streaming.ShardChainStream`,
    :func:`repro.api.streaming.stream_sample`, the checkpoint subscriber,
    :meth:`Pipeline.stream_combine`) program against exactly this surface —
    a new backend that implements it streams, checkpoints, and fuses with
    zero driver changes.
    """

    kind: str  # "vmap" | "mesh"
    cache_key: Tuple  # compile-relevant statics (keys the fused-program cache)

    @property
    def collectives_checked(self) -> Optional[int]:
        """HLO collectives verified chain-local so far (None ⇒ no assert)."""

    def backend_id(self, mode: Optional[str] = None) -> str:
        """This backend's :class:`BackendId` string for ``mode``."""

    def setup(self, shards, counts, keys):
        """Jitted init + warmup + burn-in → ``(state, eps, k_collect)``."""

    def next_chunk(self, shards, counts, eps, state, keys):
        """Jitted chunk program → ``(state, theta (M, C, d), accept (M,))``;
        must be callable under an outer trace (the fused program scans it).
        Concrete calls run the backend's HLO-assert hook lazily."""

    def prepare(self, shards, counts, keys):
        """One-time device placement of the stage inputs."""

    def put_carry(self, carry: PyTree) -> PyTree:
        """Device placement of a restored checkpoint carry."""

    def localize(self, tree: PyTree) -> PyTree:
        """Bring an emitted chunk onto the default single-device layout
        before it reaches subscribers (combiner folds, checkpoint saves):
        device sharding is an execution detail and must not leak into
        subscriber numerics — the same chunk values must fold to the same
        combiner state on every backend."""

    def run_fused(self, prog_key: Tuple, prog, shards, counts, keys):
        """Execute a fused whole-run program (jitted ``run(shards, counts,
        keys) -> (theta, accept_sum)``), applying the backend's compilation
        strategy and HLO assert; cached per ``prog_key``."""


def _setup_one(sk: ShardKernel, shard, count, key, *, burn_in, warmup, step_size):
    """Warmup + burn-in for one shard; mirrors ``run_shard_chain``'s RNG
    discipline exactly so chunked draws match the one-shot path bitwise."""
    k_init, k_run = jax.random.split(key)
    pos0 = sk.init_position(k_init, shard)
    prepared = sk.prepare(shard)
    if sk.adaptive and warmup > 0:
        k_run, k_warm = jax.random.split(k_run)
        kernel, pos0, eps = warmup_chain(
            k_warm,
            lambda e: sk.build(prepared, count, e),
            pos0,
            warmup,
            initial_step_size=step_size,
            target_accept=sk.target_accept,
        )
        burn = burn_in
    else:
        eps = jnp.asarray(step_size, jnp.float32)
        kernel = sk.build(prepared, count, step_size)
        burn = burn_in + (0 if sk.adaptive else warmup)
    state = kernel.init(pos0)
    if burn > 0:
        keys = jax.random.split(k_run, burn + 1)
        k_run = keys[0]

        def warm(s, k):
            s, _ = kernel.step(k, s)
            return s, None

        state, _ = jax.lax.scan(warm, state, keys[1:])
    return state, eps, k_run


def _chunk_one(sk: ShardKernel, shard, count, eps, state, keys):
    """Advance one chain by ``len(keys)`` draws from a live kernel state."""
    kernel = sk.build(sk.prepare(shard), count, eps)

    def collect(s, k):
        s, info = kernel.step(k, s)
        return s, (s.position, info.is_accepted)

    state, (pos, acc) = jax.lax.scan(collect, state, keys)
    return state, sk.extract(pos), acc.astype(jnp.float32).sum()


def _freeze_options(options) -> Tuple:
    items = options.items() if hasattr(options, "items") else options
    return tuple(sorted((str(k), v) for k, v in items))


def _is_traced(*trees) -> bool:
    return any(
        isinstance(leaf, jax.core.Tracer)
        for tree in trees
        for leaf in jax.tree.leaves(tree)
    )


class VmapChunkBackend:
    """M chains vmapped on one device — the default chunk backend.

    ``setup(shards, counts, keys) -> (state, eps, k_collect)`` and
    ``next_chunk(shards, counts, eps, state, keys) -> (state, theta, acc)``
    are the jitted per-chunk programs every driver composes; both are safe
    to call under an outer trace (the fused whole-run program scans
    ``next_chunk``).
    """

    kind = "vmap"

    def __init__(self, sk: ShardKernel, axes, *, burn_in, warmup, step_size,
                 cache_key: Tuple):
        self.cache_key = cache_key
        self.setup = jax.jit(
            jax.vmap(
                functools.partial(
                    _setup_one, sk,
                    burn_in=burn_in, warmup=warmup, step_size=step_size,
                ),
                in_axes=(axes, 0, 0),
            )
        )
        self._chunk = jax.jit(
            jax.vmap(
                functools.partial(_chunk_one, sk),
                in_axes=(axes, 0, 0, 0, 0),
            )
        )

    @property
    def collectives_checked(self) -> Optional[int]:
        return None  # single-device program — no collectives to assert

    def backend_id(self, mode: Optional[str] = None) -> str:
        return BackendId.vmap(mode)

    def next_chunk(self, shards, counts, eps, state, keys):
        return self._chunk(shards, counts, eps, state, keys)

    def prepare(self, shards, counts, keys):
        """Device placement hook — a no-op off the mesh."""
        return shards, counts, keys

    def put_carry(self, carry: PyTree) -> PyTree:
        """Restored-checkpoint placement hook — jit resharding suffices."""
        return carry

    def localize(self, tree: PyTree) -> PyTree:
        """Chunks already live on the one default device."""
        return tree

    def run_fused(self, prog_key: Tuple, prog, shards, counts, keys):
        return prog(shards, counts, keys)


class MeshChunkBackend:
    """The same chunk programs ``shard_map``-ped over the mesh data axis.

    Each device owns ``M/ndata`` chains + their data shards (broadcast
    leaves replicated). Every compiled program this backend runs — the
    chunk program (lazily, once per chunk shape) and the fused whole-run
    program — has its post-SPMD HLO asserted collective-free across chain
    groups via :func:`repro.distributed.epmcmc.assert_no_cross_chain_collectives`,
    the machine-checked "embarrassingly parallel" property the one-shot
    path established. ``collectives_checked`` accumulates across programs.
    """

    kind = "mesh"

    def __init__(self, model: BayesModel, sk: ShardKernel, axes, shards,
                 mesh_shape: Tuple[int, int], *, burn_in, warmup, step_size,
                 check_hlo: bool, cache_key: Tuple):
        from jax.sharding import PartitionSpec as P

        need = int(mesh_shape[0]) * int(mesh_shape[1])
        ndev = jax.device_count()
        if need > ndev:
            raise ValueError(
                f"mesh_shape={tuple(mesh_shape)} needs {need} devices but "
                f"only {ndev} are visible — launch with e.g. "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={need} "
                "(or drop mesh_shape for the vmap backend)"
            )
        self.cache_key = cache_key
        self.mesh_shape = tuple(int(x) for x in mesh_shape)
        self.mesh = chain_mesh(self.mesh_shape, ("data", "model"))
        self._check_hlo = check_hlo
        self._checked: set = set()
        self._n_checked = 0
        self._fused: Dict[Tuple, Any] = {}
        self._shard_specs = _shard_axes(shards, model.shard_keys, P("data"), P())
        self._data_spec = P("data")

        setup_v = jax.vmap(
            functools.partial(
                _setup_one, sk,
                burn_in=burn_in, warmup=warmup, step_size=step_size,
            ),
            in_axes=(axes, 0, 0),
        )
        chunk_v = jax.vmap(
            functools.partial(_chunk_one, sk), in_axes=(axes, 0, 0, 0, 0)
        )
        self.setup = jax.jit(
            jax.shard_map(
                setup_v,
                mesh=self.mesh,
                in_specs=(self._shard_specs, P("data"), P("data")),
                out_specs=P("data"),
                check_vma=False,
            )
        )
        self._chunk = jax.jit(
            jax.shard_map(
                chunk_v,
                mesh=self.mesh,
                in_specs=(
                    self._shard_specs, P("data"), P("data"), P("data"),
                    P("data"),
                ),
                out_specs=P("data"),
                check_vma=False,
            )
        )

    @property
    def collectives_checked(self) -> Optional[int]:
        return self._n_checked if self._check_hlo else None

    def backend_id(self, mode: Optional[str] = None) -> str:
        return BackendId.mesh(self.mesh_shape[0], mode)

    def _assert_hlo(self, hlo_text: str) -> None:
        # late import: epmcmc pulls the heavy LM stack
        from repro.distributed.epmcmc import assert_no_cross_chain_collectives

        self._n_checked += assert_no_cross_chain_collectives(
            hlo_text, self.mesh
        )

    def next_chunk(self, shards, counts, eps, state, keys):
        # the per-chunk HLO assert: lazily, once per chunk shape, and only
        # outside a trace (the fused program scans this method — its whole-
        # run HLO is asserted by run_fused instead)
        if self._check_hlo and not _is_traced(shards, eps, state, keys):
            shape_key = ("chunk", keys.shape)
            if shape_key not in self._checked:
                self._checked.add(shape_key)
                compiled = self._chunk.lower(
                    shards, counts, eps, state, keys
                ).compile()
                self._assert_hlo(compiled.as_text())
        return self._chunk(shards, counts, eps, state, keys)

    def _put(self, tree, specs):
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        # P subclasses tuple, so test it before the container check — a bare
        # spec broadcasts over the tree rather than flattening as one
        if isinstance(specs, P) or not isinstance(specs, (dict, list, tuple)):
            specs = jax.tree.map(lambda _: specs, tree)
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
            tree, specs,
        )

    def prepare(self, shards, counts, keys):
        """Commit the stage inputs to the mesh once, so every chunk (and the
        AOT-compiled fused program) runs without per-call redistribution."""
        return (
            self._put(shards, self._shard_specs),
            self._put(counts, self._data_spec),
            self._put(keys, self._data_spec),
        )

    def put_carry(self, carry: PyTree) -> PyTree:
        """Re-commit a restored (host) checkpoint carry to the mesh — every
        leaf carries the leading chain axis, sharded over ``data``."""
        return self._put(carry, self._data_spec)

    def localize(self, tree: PyTree) -> PyTree:
        """De-shard an emitted chunk onto the default device. Subscriber
        math (combiner folds) must be bitwise the vmap backend's for equal
        chunk values, and a mesh-sharded operand compiles to different HLO
        — so chunks leave the mesh before anyone computes on them."""
        return jax.tree.map(lambda x: jnp.asarray(jax.device_get(x)), tree)

    def run_fused(self, prog_key: Tuple, prog, shards, counts, keys):
        """AOT-compile the fused whole-run program once per key, assert its
        HLO collective-free, then run the compiled executable directly (the
        inputs were committed by :meth:`prepare`, so shardings match)."""
        compiled = self._fused.get(prog_key)
        if compiled is None:
            compiled = prog.lower(shards, counts, keys).compile()
            if self._check_hlo:
                self._assert_hlo(compiled.as_text())
            self._fused[prog_key] = compiled
        return compiled(shards, counts, keys)


# Per-process backend cache, keyed by every compile-relevant static (plus
# the backend kind/mesh): repeated Pipeline/stream instantiations re-trace
# nothing, and each mesh program's HLO assert runs once per process.
_BACKEND_CACHE: Dict[Tuple, Any] = {}


def get_chunk_backend(
    model: BayesModel,
    num_shards: int,
    sampler: str,
    *,
    warmup: int = 200,
    burn_in: int = 0,
    step_size: float = 0.1,
    sgld_batch: int = 256,
    sampler_options=(),
    use_counts: bool = True,
    shards: PyTree,
    mesh_shape: Optional[Sequence[int]] = None,
    check_hlo: bool = True,
):
    """Resolve (and cache) the chunk backend for one sampling configuration.

    ``mesh_shape=None`` (or a data axis of 1) selects the vmap backend;
    anything else the mesh backend. ``shards`` is a structure template only
    — per-leaf vmap axes / partition specs depend on the model's
    ``shard_keys``, never on shard contents or batch size (the launch path
    drives the same cached backend with rank-local slices).
    """
    use_mesh = mesh_shape is not None and int(mesh_shape[0]) > 1
    base_key = (
        model.name, sampler, num_shards, warmup, burn_in, float(step_size),
        sgld_batch, _freeze_options(sampler_options), use_counts,
    )
    cache_key = base_key + (
        ("mesh", tuple(int(x) for x in mesh_shape), bool(check_hlo))
        if use_mesh
        else ("vmap",)
    )
    backend = _BACKEND_CACHE.get(cache_key)
    if backend is None:
        sk = make_shard_kernel(
            model,
            num_shards,
            sampler,
            sgld_batch=sgld_batch,
            use_counts=use_counts,
            sampler_options=sampler_options,
        )
        axes = _shard_axes(shards, model.shard_keys, 0, None)
        if use_mesh:
            backend = MeshChunkBackend(
                model, sk, axes, shards, tuple(mesh_shape),
                burn_in=burn_in, warmup=warmup, step_size=step_size,
                check_hlo=check_hlo, cache_key=cache_key,
            )
        else:
            backend = VmapChunkBackend(
                sk, axes,
                burn_in=burn_in, warmup=warmup, step_size=step_size,
                cache_key=cache_key,
            )
        _BACKEND_CACHE[cache_key] = backend
    return backend
