"""Staged, resumable execution of one :class:`RunSpec`.

The paper's dataflow is fixed — **partition → sample → combine → score** —
so the Pipeline exposes exactly those stages, each returning an explicit
typed artifact that can be inspected, persisted, or fed onward:

    ``partition() -> ShardedData``             (M shards + valid-row counts)
    ``sample()    -> SubposteriorDraws``       ((M, T, d) θ + diagnostics)
    ``combine()   -> dict[str, CombineResult]``(one per requested combiner)
    ``score()     -> Scoreboard``              (error per combiner vs groundtruth)

Stages are lazy and cached: each runs its predecessors on demand, so
``Pipeline(spec).run()`` is the whole paper and ``pipe.sample()`` alone is
just the embarrassingly parallel stage. RNG discipline is fixed by the spec
seed (data from ``PRNGKey(seed)``, sampling from ``fold_in(key, 1)``,
groundtruth ``fold_in(key, 2)``, one independent stream per combiner from
``fold_in(key, 3)`` + a stable hash of the name), so the same spec always
produces bitwise-identical artifacts.

The sampling stage always runs the chunk-emitting driver of
:mod:`repro.api.streaming`: chunks of ``spec.stream_every`` draws (one
T-sized chunk when 0) land in order, and everything else subscribes —
checkpoint persistence (``checkpoint_dir`` / ``checkpoint_every``, resume
mid-chain bitwise), and **combine-while-sampling** via
:meth:`Pipeline.stream_combine`, which folds every landed chunk into the
requested streaming combiners
(:func:`repro.core.combiners.get_streaming_combiner`), records a per-chunk
scoreboard trajectory, and finalizes estimates that are bitwise the
gather-then-combine result for the buffered combiners. Which *execution
backend* emits the chunks is a :mod:`repro.api.backends` decision: the
vmap backend on one device, or — ``mesh_shape`` (explicit or the >1-device
auto-mesh, :func:`repro.api.backends.auto_mesh_shape`) — the mesh chunk
backend, which ``shard_map``\\ s the same chunk programs over chain groups
and asserts each compiled program's HLO collective-free across chains.

The batch combination stage resolves each combiner by registry name
(:func:`repro.core.combiners.get_combiner`), with the options each one's
signature declares.
"""

from __future__ import annotations

import math
import time
import zlib
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.api.backends import auto_mesh_shape
from repro.api.spec import RunSpec
from repro.api.sampling import groundtruth_chain
from repro.api.streaming import StreamChunk, stream_sample
from repro.core import metrics
from repro.core.subposterior import partition_data
from repro.core.combiners import (
    BufferState,
    CombineResult,
    StreamingCombiner,
    filter_options,
    get_combiner,
    get_scan_face,
    get_streaming_combiner,
)
from repro.models.bayes import get_model
from repro.samplers import sampler_spec
from repro.utils.spans import span

PyTree = Any

# models at or above this θ-dimension are scored in log space: raw
# `l2_distance` enters the f32-overflow regime of the KDE normalizer there
# (its own docstring's warning) and becomes hypersensitive to dispersion
LOG_L2_DIM = 40


def groundtruth_step_size(spec: RunSpec) -> float:
    """Full-chain step compensation, shared by Pipeline and run_matrix.

    The full posterior is ~√M narrower than a subposterior and its gradient
    M× larger; warmup absorbs that for adaptive kernels, fixed-step ones
    need the classic compensation (ε/M for Langevin time steps, ε/√M for
    proposal scales).
    """
    sp = sampler_spec(spec.resolved_sampler())
    if sp.name == "sgld":
        return spec.step_size / spec.M
    if not (sp.adaptive and spec.warmup > 0):
        return spec.step_size / math.sqrt(spec.M)
    return spec.step_size


def gather_draws(theta: jnp.ndarray) -> jnp.ndarray:
    """The draws on one device, as the combine stage takes them.

    On a mesh the chains' draws stay sharded over the data axis. Combiners
    are single-device programs, and XLA cannot partition the Pallas kernels
    inside them (Mosaic refuses a sharded operand), so combining starts by
    gathering the draws: the one communication step of the method.
    """
    if len(theta.sharding.device_set) == 1:
        return theta
    return jax.device_put(theta, jax.devices()[0])


@span("combine.stage")
def combine_spec_draws(
    spec: RunSpec,
    base_key: jax.Array,
    theta: jnp.ndarray,
    names: Optional[Tuple[str, ...]] = None,
) -> "Dict[str, CombineResult]":
    """The combine stage for one spec, shared by Pipeline and run_matrix.

    One independent RNG stream per estimator (``fold_in(base_key, 3)`` then
    a fold by a stable hash of the name — one shared key would correlate the
    scoreboard entries, and it also makes each combiner's result independent
    of which subset ``names`` selects); options merge the spec's
    ``combiner_options`` over the driver defaults and are filtered per
    combiner signature (:func:`combine_draws`). The call is the
    ``combine.stage`` span (:mod:`repro.utils.spans`), each combiner a
    ``combine.<name>`` span inside it.
    """
    theta = gather_draws(theta)
    kc = jax.random.fold_in(base_key, 3)
    options = dict({"rescale": True, "n_batch": 1}, **dict(spec.combiner_options))
    out: Dict[str, CombineResult] = {}
    for name in names if names is not None else spec.combiner_names():
        k_name = jax.random.fold_in(kc, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        with span(f"combine.{name}"):
            out[name] = combine_draws(
                k_name, theta, spec.T, combiner=name, **options
            )
    return out


def resolve_metric(spec: RunSpec, d: int):
    """``(distance_fn, label)`` for a spec: ``score_metric`` override or the
    dimension rule above (narrow posteriors can force ``"logl2"`` explicitly
    — e.g. the scenario-matrix CI cells on the linear exactness oracle)."""
    use_log = spec.score_metric == "logl2" or (
        spec.score_metric == "auto" and d >= LOG_L2_DIM
    )
    if use_log:
        return metrics.log_l2_distance, "logL2"
    return metrics.l2_distance, "L2"


class ShardedData(NamedTuple):
    """Partition-stage artifact: the paper's M "machines" worth of data."""

    shards: PyTree  # per-datum leaves carry a leading (M, ...) chain axis
    counts: jnp.ndarray  # (M,) real rows per shard (edge-pad convention)
    data: PyTree  # the full dataset (groundtruth stage input)
    theta_true: jnp.ndarray  # generating parameters (diagnostics only)


class SubposteriorDraws(NamedTuple):
    """Sampling-stage artifact: M independent subposterior chains."""

    theta: jnp.ndarray  # (M, T, d) shared-θ draws
    accept: jnp.ndarray  # (M,) mean acceptance per chain
    counts: jnp.ndarray  # (M,)
    backend: str  # a repro.api.backends.BackendId string ("vmap[chunked]",
    # "shard_map[fused](4 devices)", ...) — never assembled ad hoc
    collectives_checked: Optional[int]
    t_done: int  # draws collected so far (== T unless interrupted)
    complete: bool


class StreamResult(NamedTuple):
    """Artifact of :meth:`Pipeline.stream_combine` (combine-while-sampling).

    ``trajectory`` rows are ``{"t", "combiner", "error", "elapsed_s"}`` —
    one per (chunk boundary, combiner-with-a-cheap-``estimate``), in
    landing order (fallback-streamed combiners fold every chunk but only
    finalize, so they contribute no rows); ``elapsed_s`` is
    wall time since the stream started, stamped per row when that row's
    estimate has actually materialized (``block_until_ready`` before the
    clock read) — so it is monotone in landing order and honest in both
    modes (``trajectory[0]["elapsed_s"]`` is the time-to-first-estimate the
    bench tracks; on a resumed run the replayed prefix carries the resume
    session's clock; on the fused path the one compiled combine-fold
    program materializes estimates close together, so consecutive stamps
    can be near-identical — but each is still that row's true availability
    instant). ``combined`` holds
    the finalized per-combiner results (empty while ``complete`` is False).
    """

    combined: Dict[str, CombineResult]
    trajectory: List[Dict[str, Any]]
    t_done: int
    total: int
    complete: bool
    metric: str  # "L2" | "logL2" | "" when unscored
    stream_every: int
    n_estimate: int


class StreamSetup(NamedTuple):
    """Resolved combine-while-sampling surfaces for one stream consumer.

    The shared setup of everything that folds the chunk stream —
    :meth:`Pipeline.stream_combine` and the ``repro.serve`` query layer —
    so both consume identical streaming combiners, per-name RNG streams
    (``fold_in(key, 3)`` + stable name hash, the combine stage's
    discipline), and merged options. Anything folding the same chunks
    through the same setup reproduces the trajectory estimates bitwise.
    """

    names: Tuple[str, ...]
    combiners: Dict[str, StreamingCombiner]
    keys: Dict[str, jax.Array]  # name -> independent RNG stream
    options: Dict[str, Any]  # merged spec.combiner_options over defaults


class Scoreboard(NamedTuple):
    """Score-stage artifact: the paper's error table for one scenario."""

    spec_id: str
    model: str
    sampler: str
    M: int
    T: int
    metric: str  # "L2" | "logL2"
    errors: Dict[str, float]  # combiner name -> distance to groundtruth
    accept: float
    backend: str
    collectives_checked: Optional[int]
    timings: Dict[str, float]  # stage -> seconds

    def table(self) -> str:
        lines = [
            f"model={self.model} M={self.M} T={self.T} sampler={self.sampler} "
            f"acc={self.accept:.2f} backend={self.backend}"
        ]
        for name, err in sorted(self.errors.items(), key=lambda kv: kv[1]):
            lines.append(f"  {self.metric}({name:15s}) = {err:.4f}")
        return "\n".join(lines)

    def to_dict(self) -> Dict[str, Any]:
        return dict(self._asdict())


class Pipeline:
    """Run one :class:`RunSpec` stage by stage (see module docstring)."""

    def __init__(
        self,
        spec: RunSpec,
        *,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 0,
        check_hlo: bool = True,
    ):
        self.spec = spec.validate()
        self.checkpoint_dir = str(checkpoint_dir) if checkpoint_dir else None
        if checkpoint_every > 0 and self.checkpoint_dir is None:
            raise ValueError(
                "checkpoint_every > 0 without a checkpoint_dir would "
                "silently persist nothing — pass checkpoint_dir (or drop "
                "the cadence)"
            )
        self.checkpoint_every = checkpoint_every
        self.check_hlo = check_hlo
        self.timings: Dict[str, float] = {}  # from the pipeline.* spans
        self._model = get_model(spec.model)
        self._key = jax.random.PRNGKey(spec.seed)
        self._sharded: Optional[ShardedData] = None
        self._draws: Optional[SubposteriorDraws] = None
        self._groundtruth: Optional[jnp.ndarray] = None
        self._combined: Optional[Dict[str, CombineResult]] = None
        self._board: Optional[Scoreboard] = None

    # -- stage 1: partition --------------------------------------------------

    def partition(self) -> ShardedData:
        if self._sharded is None:
            model, spec = self._model, self.spec
            data, theta_true = model.generate_data(self._key, spec.resolved_n())
            shards, counts = partition_data(
                data, spec.M, only=model.shard_keys, pad=True
            )
            self._sharded = ShardedData(shards, counts, data, theta_true)
        return self._sharded

    # -- stage 2: sample (embarrassingly parallel) ---------------------------

    def sample(
        self,
        max_steps: Optional[int] = None,
        on_chunk: Sequence[Callable[[StreamChunk], None]] = (),
    ) -> SubposteriorDraws:
        """Run (or resume) the M subposterior chains as one chunk stream.

        ``max_steps`` bounds the draws collected *this call* (checkpointed
        runs only) — the budgeted-sampling / preemption-simulation hook. A
        partial artifact has ``complete=False``; calling ``sample()`` again
        continues from the persisted kernel state. ``on_chunk`` subscribers
        see every landed ``(M, C, d)`` chunk in order, restored prefixes
        included (:meth:`stream_combine` is the built-in subscriber).

        Backend routing: the chunk-emitting driver
        (:func:`repro.api.streaming.stream_sample`) on the backend the
        spec's ``mesh_shape`` selects (explicit, or the >1-device auto-mesh
        when M divides evenly), with per-program HLO asserts on a mesh.
        """
        if self._draws is not None and self._draws.complete:
            return self._draws
        if max_steps is not None and self.checkpoint_dir is None:
            raise ValueError(
                "max_steps needs a checkpoint_dir: a partial sampling "
                "stage is only useful if it can be resumed"
            )
        spec = self.spec
        sharded = self.partition()
        with span("pipeline.sample") as rec:
            rs = stream_sample(
                jax.random.fold_in(self._key, 1),
                self._model,
                sharded.data,
                spec.M,
                spec.T,
                sampler=spec.sampler,
                warmup=spec.warmup,
                burn_in=spec.resolved_burn_in(),
                step_size=spec.step_size,
                sgld_batch=spec.sgld_batch,
                sampler_options=spec.sampler_options,
                shards=sharded.shards,
                counts=sharded.counts,
                chunk_size=spec.stream_every,
                max_steps=max_steps,
                checkpoint_dir=self.checkpoint_dir,
                checkpoint_every=self.checkpoint_every,
                spec_id=spec.spec_id,
                on_chunk=on_chunk,
                mesh_shape=auto_mesh_shape(spec.mesh_shape, spec.M),
                check_hlo=self.check_hlo,
            )
            res = rs.result
            # stage timers read the clock after the device finishes, not after
            # the enqueue
            jax.block_until_ready((res.theta, res.accept))
        self.timings["sample_s"] = self.timings.get("sample_s", 0.0) + rec.seconds
        self._draws = SubposteriorDraws(
            res.theta, res.accept, res.counts, res.backend,
            res.collectives_checked, rs.t_done, rs.complete,
        )
        return self._draws

    # -- groundtruth: single full-data chain ---------------------------------

    def groundtruth(self) -> jnp.ndarray:
        """Long full-data chain at the compensated step size
        (:func:`groundtruth_step_size`)."""
        if self._groundtruth is None:
            spec = self.spec
            gt_step = groundtruth_step_size(spec)
            with span("pipeline.groundtruth") as rec:
                self._groundtruth = groundtruth_chain(
                    jax.random.fold_in(self._key, 2),
                    self._model,
                    self.partition().data,
                    spec.groundtruth_T,
                    sampler=spec.sampler,
                    warmup=spec.warmup,
                    burn_in=spec.groundtruth_T // 6,
                    step_size=gt_step,
                    sgld_batch=spec.sgld_batch,
                    sampler_options=spec.sampler_options,
                )
                jax.block_until_ready(self._groundtruth)
            self.timings["groundtruth_s"] = rec.seconds
        return self._groundtruth

    # -- stage 3b: combine-while-sampling ------------------------------------

    def stream_setup(
        self, names: Optional[Tuple[str, ...]] = None
    ) -> StreamSetup:
        """Resolve the streaming surfaces for ``names`` (default: the
        spec's combiners) — see :class:`StreamSetup`. Fails fast on
        unknown names."""
        spec = self.spec
        names = spec.combiner_names() if names is None else tuple(names)
        scs: Dict[str, StreamingCombiner] = {}
        for name in names:
            get_combiner(name)  # fail fast on unknown names
            scs[name] = get_streaming_combiner(name)
        options = dict(
            {"rescale": True, "n_batch": 1}, **dict(spec.combiner_options)
        )
        kc = jax.random.fold_in(self._key, 3)
        k_names = {
            name: jax.random.fold_in(kc, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            for name in names
        }
        return StreamSetup(names, scs, k_names, options)

    def stream_combine(
        self,
        names: Optional[Tuple[str, ...]] = None,
        *,
        n_estimate: int = 128,
        max_steps: Optional[int] = None,
        score: bool = True,
        fused: Optional[bool] = None,
    ) -> StreamResult:
        """Fold each landed sampling chunk into the streaming combiners.

        Requires ``spec.stream_every > 0``. As every ``stream_every``-draw
        chunk lands, it is ``update``-folded into one
        :class:`~repro.core.combiners.api.StreamingCombiner` per requested
        name and a cheap ``estimate`` (``n_estimate`` draws) is taken — the
        per-chunk scoreboard trajectory. Combiners whose streaming form has
        no cheap ``estimate`` (the generic buffered fallback — weierstrass,
        rpt, …) still fold every chunk but contribute no mid-stream rows:
        re-running a heavy batch combiner on the growing buffer at every
        boundary would cost more than the gather path the stream exists to
        beat. When sampling completes, each state
        is ``finalize``\\ d with the *same* RNG stream and options as the
        batch combine stage, so the final results are bitwise the
        gather-then-combine ones for the buffered combiners (``parametric``,
        ``pool``, ``nonparametric``, every fallback) and within Welford
        merge-rounding for ``online``; :meth:`score` then reuses them.

        ``fused`` selects the hot path: ``None`` (default) fuses
        automatically when every requested combiner has a scan face
        (:func:`repro.core.combiners.get_scan_face`) and nothing needs the
        host between chunks (no checkpointing, no ``max_steps`` budget) —
        sampling runs as one compiled program shared with the gather path
        (same theta bitwise) and the combiner folds + in-scan trajectory
        estimates run as a second compiled program over the device-resident
        draws (:func:`repro.api.streaming.fused_fold`), zero per-chunk host
        hops. ``fused=False`` forces the subscriber-driven path;
        ``fused=True`` asserts fusability and raises when the run needs the
        subscriber path. Finals are bitwise identical between the two modes
        (same theta, same keys, same host ``finalize``); trajectory
        estimates agree to compile-scheduling rounding, and ``online``'s
        fused folds to Welford merge-rounding (its scan face runs the
        Pallas ``online_update`` kernel).

        ``score=False`` skips the groundtruth chain and leaves trajectory
        errors ``None`` (the bench's time-to-first-estimate mode);
        ``max_steps`` bounds this session (checkpointed runs — a later
        ``stream_combine`` on the same directory replays the restored
        prefix and reproduces the uninterrupted trajectory exactly).
        """
        spec = self.spec
        if spec.stream_every <= 0:
            raise ValueError(
                "stream_combine needs RunSpec.stream_every > 0 — with no "
                "chunk cadence there is nothing to fold mid-run (set e.g. "
                "stream_every=T//10, or use combine())"
            )
        names, scs, k_names, options = self.stream_setup(names)

        faces = {name: get_scan_face(name) for name in names}
        can_fuse = (
            fused is not False
            and self.checkpoint_dir is None
            and max_steps is None
            and all(faces[name] is not None for name in names)
        )
        if fused is True and not can_fuse:
            blockers = [n for n in names if faces[n] is None]
            raise ValueError(
                "fused=True but this run needs the subscriber path: "
                + (
                    f"combiners without a scan face: {blockers}"
                    if blockers
                    else "checkpointing/max_steps require per-chunk host "
                    "subscribers"
                )
            )
        if can_fuse:
            return self._stream_combine_fused(
                names, scs, faces, k_names, options, n_estimate, score
            )
        states: Dict[str, Any] = {name: None for name in names}
        rows: List[Dict[str, Any]] = []
        estimates: List[Tuple[int, str, jnp.ndarray]] = []
        t_start = time.time()

        def fold(ev: StreamChunk) -> None:
            M, _, d = ev.theta.shape
            for name in names:
                sc = scs[name]
                if states[name] is None:
                    states[name] = sc.init(M, d)
                states[name] = sc.update(states[name], ev.theta)
            for name in names:
                est_fn = scs[name].estimate
                if est_fn is None:
                    continue  # no cheap mid-stream estimate — finalize-only
                k_est = jax.random.fold_in(k_names[name], ev.t1)
                est = est_fn(
                    k_est, states[name], n_estimate,
                    **filter_options(est_fn, options),
                )
                est.samples.block_until_ready()  # honest elapsed_s
                if score:
                    estimates.append((ev.t1, name, est.samples))
                rows.append({
                    "t": ev.t1,
                    "combiner": name,
                    "error": None,
                    "elapsed_s": time.time() - t_start,
                })

        if self._draws is not None and self._draws.complete:
            # sampling already ran (e.g. combine() first): replay the cached
            # draws at the stream cadence — same chunks, same states
            theta = self._draws.theta
            zeros = jnp.zeros((spec.M,), jnp.float32)
            for r0 in range(0, spec.T, spec.stream_every):
                r1 = min(r0 + spec.stream_every, spec.T)
                fold(StreamChunk(
                    theta[:, r0:r1], zeros, r0, r1, spec.T, {}, replayed=True
                ))
            draws = self._draws
        else:
            draws = self.sample(max_steps=max_steps, on_chunk=(fold,))

        final: Dict[str, CombineResult] = {}
        if draws.complete:
            with span("pipeline.stream_combine") as rec:
                for name in names:
                    fn = scs[name].finalize
                    final[name] = fn(
                        k_names[name], states[name], spec.T,
                        **filter_options(fn, options),
                    )
                jax.block_until_ready(final)
            self.timings["stream_combine_s"] = rec.seconds
            # the finals ARE the combine-stage results (bitwise for the
            # buffered implementations) — let score() reuse them
            if self._combined is None and set(names) == set(spec.combiner_names()):
                self._combined = dict(final)
                self.timings.setdefault("combine_s", rec.seconds)

        label = ""
        if score:
            gt = self.groundtruth()
            dist, label = resolve_metric(spec, self._model.d)
            for row, (_, _, samples) in zip(rows, estimates):
                row["error"] = float(dist(gt, samples))
        return StreamResult(
            combined=final,
            trajectory=rows,
            t_done=draws.t_done,
            total=spec.T,
            complete=draws.complete,
            metric=label,
            stream_every=spec.stream_every,
            n_estimate=n_estimate,
        )

    def _stream_combine_fused(
        self,
        names: Tuple[str, ...],
        scs: Dict[str, Any],
        faces: Dict[str, Any],
        k_names: Dict[str, jax.Array],
        options: Dict[str, Any],
        n_estimate: int,
        score: bool,
    ) -> StreamResult:
        """The fused mode of :meth:`stream_combine`: one compiled sampling
        program (shared with the plain stage — same theta bitwise), one
        compiled combine-fold program over the device-resident draws.

        Trajectory rows land for exactly the combiners the subscriber path
        would estimate (host ``estimate`` non-None), in the same
        per-boundary order and from the same ``fold_in(k_name, t1)`` keys:
        in-scan for faces shipping a scan ``estimate`` (``parametric``),
        post-hoc on buffered prefixes of the gathered draws for the rest
        (``pool``, ``nonparametric``, ...).
        """
        from repro.api.streaming import fused_fold

        spec = self.spec
        t_start = time.time()
        draws = self.sample()  # the fused program, or the cached draws
        theta = gather_draws(draws.theta)
        chunk = spec.stream_every
        counts_T = jnp.full((spec.M,), spec.T, jnp.int32)

        with span("pipeline.stream_combine") as rec:
            n_full, tail = divmod(spec.T, chunk)
            boundaries = tuple(chunk * (i + 1) for i in range(n_full)) + (
                (spec.T,) if tail else ()
            )
            est_keys = {
                name: jnp.stack(
                    [jax.random.fold_in(k_names[name], t1) for t1 in boundaries]
                )
                for name in names
                if faces[name].estimate is not None and scs[name].estimate is not None
            }
            ff = fused_fold(
                theta, {n: faces[n] for n in names}, est_keys, n_estimate,
                chunk, options,
            )

            rows: List[Dict[str, Any]] = []
            estimates: List[Tuple[int, str, jnp.ndarray]] = []
            for i, t1 in enumerate(ff.boundaries):
                for name in names:
                    est_fn = scs[name].estimate
                    if est_fn is None:
                        continue  # no mid-stream row on the subscriber path either
                    if name in est_keys:
                        samples = ff.est_draws[name][i]
                    else:
                        prefix = BufferState(
                            theta[:, :t1], jnp.full((spec.M,), t1, jnp.int32)
                        )
                        samples = est_fn(
                            jax.random.fold_in(k_names[name], t1), prefix,
                            n_estimate, **filter_options(est_fn, options),
                        ).samples
                    estimates.append((t1, name, samples))
                    rows.append({
                        "t": t1, "combiner": name, "error": None, "elapsed_s": None,
                    })
            # honest per-boundary stamps: each row's clock reads only after THAT
            # row's estimate is device-complete, so elapsed_s is the row's true
            # availability instant (monotone in landing order) — not one post-run
            # stamp smeared across the trajectory. The fused program materializes
            # estimates close together, so consecutive stamps may be near-equal;
            # they are still each row's own wall-clock.
            for row, (_, _, samples) in zip(rows, estimates):
                jax.block_until_ready(samples)
                row["elapsed_s"] = time.time() - t_start

            final: Dict[str, CombineResult] = {}
            for name in names:
                fn = scs[name].finalize
                host_state = faces[name].to_state(ff.states[name], theta, counts_T)
                final[name] = fn(
                    k_names[name], host_state, spec.T,
                    **filter_options(fn, options),
                )
            jax.block_until_ready(final)
        self.timings["stream_combine_s"] = rec.seconds
        if self._combined is None and set(names) == set(spec.combiner_names()):
            self._combined = dict(final)
            self.timings.setdefault("combine_s", rec.seconds)

        label = ""
        if score:
            gt = self.groundtruth()
            dist, label = resolve_metric(spec, self._model.d)
            for row, (_, _, samples) in zip(rows, estimates):
                row["error"] = float(dist(gt, samples))
        return StreamResult(
            combined=final,
            trajectory=rows,
            t_done=draws.t_done,
            total=spec.T,
            complete=True,
            metric=label,
            stream_every=spec.stream_every,
            n_estimate=n_estimate,
        )

    # -- stage 3: combine (the only communicating stage) ---------------------

    def combine(self) -> Dict[str, CombineResult]:
        if self._combined is None:
            spec = self.spec
            draws = self.sample()
            if not draws.complete:
                raise RuntimeError(
                    f"sampling stage incomplete ({draws.t_done}/{spec.T} "
                    "draws) — call sample() until complete before combine()"
                )
            with span("pipeline.combine") as rec:
                self._combined = combine_spec_draws(spec, self._key, draws.theta)
                jax.block_until_ready(self._combined)
            self.timings["combine_s"] = rec.seconds
        return self._combined

    # -- stage 4: score ------------------------------------------------------

    def score(self) -> Scoreboard:
        if self._board is None:
            spec = self.spec
            combined = self.combine()
            gt = self.groundtruth()
            # high-d runs score in log space (f32-overflow regime of raw L2)
            dist, label = resolve_metric(spec, self._model.d)
            errors = {
                name: float(dist(gt, res.samples))
                for name, res in combined.items()
            }
            draws = self._draws
            self._board = Scoreboard(
                spec_id=spec.spec_id,
                model=spec.model,
                sampler=spec.resolved_sampler(),
                M=spec.M,
                T=spec.T,
                metric=label,
                errors=errors,
                accept=float(jnp.mean(draws.accept)),
                backend=draws.backend,
                collectives_checked=draws.collectives_checked,
                timings=dict(self.timings),
            )
        return self._board

    def run(self) -> Scoreboard:
        """All four stages; equivalent to the historical ``mcmc_run`` body."""
        return self.score()


def combine_draws(
    key: jax.Array,
    samples: jnp.ndarray,
    n_draws: int,
    *,
    combiner: str = "nonparametric",
    **options,
) -> CombineResult:
    """Registry-dispatched combination of a dense ``(M, T, d)`` stack.

    The combine stage's dispatch (``Pipeline.combine()`` runs every
    combiner through it), and its programmatic face for callers that
    already hold subposterior draws. The combiner is resolved by registry
    name; options its signature does not declare are dropped, so one option
    dict can drive rival combiners.
    """
    fn = get_combiner(combiner)
    return fn(key, samples, n_draws, **filter_options(fn, options))
