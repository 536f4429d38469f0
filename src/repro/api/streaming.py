"""The one chunk-emitting sampling driver behind every ``repro.api`` run.

**One** generator (:meth:`ShardChainStream.chunks`) advances all M chains in
global chunks and yields each landed ``(M, C, d)`` slice, and everything
else subscribes —

- checkpoint persistence (restore/validation in :func:`stream_sample` and a
  save-at-boundary subscriber);
- streaming combination (``Pipeline.stream_combine`` folds every chunk into
  the registered :class:`~repro.core.combiners.api.StreamingCombiner`\\ s);
- the plain sampling stage (one chunk of T draws when neither is asked for:
  ``Pipeline.sample`` and :func:`repro.api.sampling.sample_subposteriors`).

The bitwise-resume guarantee is structural: per-step RNG keys
are a pure function of the seed, chunk boundaries are global multiples of
the cadence, and sessions advance in whole chunks — so an interrupted-then-
resumed run replays exactly the same chunk programs on the same inputs as
one that never stopped.

Execution is delegated to a pluggable :mod:`repro.api.backends`
:class:`~repro.api.backends.ChunkBackend`: the vmap backend on one device,
or — ``mesh_shape=`` with a data axis > 1 — the mesh backend, which
``shard_map``\\ s the *same* chunk programs over chain groups and asserts
every compiled program's HLO collective-free across chains (per chunk
shape, and for the fused whole-run program). Checkpointing, streaming
combination, and the fused fold subscribe identically on either backend.

Fused hot path: when nobody subscribes (no checkpointing, no ``on_chunk``,
no budget) a chunked run pays the host loop for nothing — every chunk is a
device→host→device round trip of pure dispatch overhead. ``stream_sample``
then runs :meth:`ShardChainStream.fused_program` instead: setup + a
``lax.scan`` over the *same* chunk programs inside ONE jitted executable
(backend tag ``"vmap[fused]"``). Two executables matter here, not one:

- the fused **sampling** program is shared by every caller at the same
  cadence — the plain sampling stage (hence the gather-then-combine path)
  and ``Pipeline.stream_combine``'s fused mode produce the *same* theta
  array from the same compiled program, which is what makes the fused
  stream's finals bitwise the gather results;
- the fused **combine-fold** program (:func:`fused_fold`) scans the
  requested combiners' :class:`~repro.core.combiners.api.ScanStreamingFace`
  updates (and in-scan trajectory estimates) over that device-resident
  theta, with the fold states donated between steps — zero per-chunk host
  hops on the combine side too.

A literal single sample+combine scan was measured and rejected: hoisting
the combine update into the sampling scan changes the XLA schedule enough
that theta drifts from the chunked driver at the last ulp (~2e-7), which
would break the bitwise gather contract. The split keeps both programs
fused end-to-end *and* keeps theta identical by construction.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Callable, Dict, Iterator, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.checkpoint import latest_step, restore, save
from repro.core.subposterior import partition_data
from repro.models.bayes import BayesModel
from repro.api.backends import CHUNKED, FUSED, RESUMABLE, get_chunk_backend
from repro.api.sampling import (
    SampleResult,
    _freeze_options,
    count_steps,
    is_padded,
    loglik_fused,
    shard_rows,
)
from repro.utils.spans import span

PyTree = Any


class StreamChunk(NamedTuple):
    """One landed chunk of subposterior draws (what subscribers consume).

    On a resumed run the restored prefix is re-emitted with
    ``replayed=True``: there ``theta``/``t0``/``t1`` are faithful per-chunk
    (sliced from the restored draws at the original boundaries), but the
    historical kernel states are gone — ``carry`` holds the *restored*
    (latest) state and ``accept`` is zeroed. Subscribers that need per-chunk
    carry/acceptance must skip replayed chunks; the streaming combiners
    consume only ``theta``.

    ``landed_s`` is the ``time.monotonic()`` instant the driver emitted the
    chunk (replays stamp their re-emission, not the original landing) — the
    honest per-boundary clock behind trajectory ``elapsed_s`` and the
    serving layer's ``last_fold_monotonic_s`` staleness field. It is
    metadata, not part of the bitwise-resume contract.
    """

    theta: jnp.ndarray  # (M, C, d) this chunk's draws
    accept: jnp.ndarray  # (M,) accepted count in the chunk (zeros if replayed)
    t0: int  # first global draw index of the chunk
    t1: int  # one past the last (t1 - t0 == C)
    total: int  # the run's T
    carry: Dict[str, jnp.ndarray]  # live driver state (restored if replayed)
    replayed: bool = False  # True when re-emitted from restored draws
    landed_s: Optional[float] = None  # monotonic emission instant (metadata)


# fused whole-run sampling programs: backend cache key + (T, chunk)
_FUSED_SAMPLE_CACHE: Dict[Tuple, Any] = {}
# fused combine-fold programs: (combiner names, chunking, shapes, options)
_FUSED_FOLD_CACHE: Dict[Tuple, Any] = {}


class ShardChainStream:
    """M parallel subposterior chains, advanced in global chunks.

    Owns the resolved :class:`~repro.api.backends.ChunkBackend` (the jitted
    setup and chunk programs, shared across instances via the backend
    cache), the mesh-committed stage inputs, and the per-step collect keys
    (a pure function of the seed — identical on every session, whatever the
    chunking).
    """

    def __init__(
        self,
        key: jax.Array,
        model: BayesModel,
        num_shards: int,
        num_samples: int,
        *,
        sampler: Optional[str] = None,
        warmup: int = 200,
        burn_in: int = 0,
        step_size: float = 0.1,
        sgld_batch: int = 256,
        sampler_options=(),
        shards: PyTree,
        counts: jnp.ndarray,
        use_counts: bool,
        mesh_shape: Optional[Tuple[int, int]] = None,
        check_hlo: bool = True,
    ):
        self.model = model
        self.num_shards = num_shards
        self.num_samples = num_samples
        sampler = sampler or model.default_sampler
        # whether each transition's gradient comes from the fused pass
        self.fused = loglik_fused(
            model, sampler, shard_rows(model, shards, axis=1)
        )
        self.backend = get_chunk_backend(
            model,
            num_shards,
            sampler,
            warmup=warmup,
            burn_in=burn_in,
            step_size=step_size,
            sgld_batch=sgld_batch,
            sampler_options=sampler_options,
            use_counts=use_counts,
            shards=shards,
            mesh_shape=mesh_shape,
            check_hlo=check_hlo,
        )
        self._cache_key = self.backend.cache_key
        self.setup = self.backend.setup
        self.chunk_fn = self.backend.next_chunk
        self.shards, self.counts, self.keys = self.backend.prepare(
            shards, counts, jax.random.split(key, num_shards)
        )

    def setup_struct(self):
        """Abstract ``(state, eps, k_collect)`` shapes — the restore template."""
        return jax.eval_shape(self.setup, self.shards, self.counts, self.keys)

    def fresh_carry(self) -> Dict[str, jnp.ndarray]:
        state, eps, k_collect = self.setup(self.shards, self.counts, self.keys)
        return {
            "state": state,
            "eps": eps,
            "k_collect": k_collect,
            "theta": jnp.zeros(
                (self.num_shards, 0, self.model.d), jnp.float32
            ),
            "accept_sum": jnp.zeros((self.num_shards,), jnp.float32),
        }

    def fused_program(self, chunk: int):
        """ONE jitted executable for the whole run: setup + ``lax.scan`` over
        the chunk programs (plus the statically-unrolled ragged tail).

        The scan body calls the *same* ``chunk_fn`` the host-driven
        :meth:`chunks` loop dispatches — whoever samples at this cadence
        through this program (the plain stage, the fused stream) gets the
        same theta from the same executable. Returns ``run(shards, counts,
        keys) -> (theta (M, T, d), accept_sum (M,))``.
        """
        T = self.num_samples
        key = self._cache_key + (T, int(chunk))
        prog = _FUSED_SAMPLE_CACHE.get(key)
        if prog is None:
            n_full, tail = divmod(T, chunk)
            setup, chunk_fn = self.setup, self.chunk_fn

            def run(shards, counts, keys):
                state, eps, k_collect = setup(shards, counts, keys)
                ck = jax.vmap(lambda k: jax.random.split(k, T))(k_collect)
                body = ck[:, : n_full * chunk]
                xs = jnp.moveaxis(
                    body.reshape(
                        (body.shape[0], n_full, chunk) + body.shape[2:]
                    ),
                    1, 0,
                )  # (n_full, M, chunk, key)

                def step(st, kc):
                    st, th, ac = chunk_fn(shards, counts, eps, st, kc)
                    return st, (th, ac)

                state, (ths, acs) = jax.lax.scan(step, state, xs)
                theta = jnp.moveaxis(ths, 0, 1).reshape(
                    ths.shape[1], n_full * chunk, ths.shape[-1]
                )
                accept = acs.sum(axis=0)
                if tail:
                    state, th_t, ac_t = chunk_fn(
                        shards, counts, eps, state,
                        ck[:, n_full * chunk :],
                    )
                    theta = jnp.concatenate([theta, th_t], axis=1)
                    accept = accept + ac_t
                return theta, accept

            prog = _FUSED_SAMPLE_CACHE[key] = jax.jit(run)
        return prog

    def fused_sample(self, chunk: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Run the fused whole-run program on this stream's inputs via the
        backend's compilation strategy (the mesh backend AOT-compiles and
        asserts the whole-run HLO collective-free before executing)."""
        prog_key = self._cache_key + (self.num_samples, int(chunk))
        return self.backend.run_fused(
            prog_key, self.fused_program(chunk),
            self.shards, self.counts, self.keys,
        )

    def chunks(
        self,
        carry: Dict[str, jnp.ndarray],
        t_done: int,
        chunk_size: int,
        stop: Optional[int] = None,
    ) -> Iterator[StreamChunk]:
        """Yield whole chunks from ``t_done`` until ``stop`` (default T).

        Boundaries are global multiples of ``chunk_size`` (+ the final T), so
        the emitted chunk *programs* are independent of where a session
        starts — the structural bitwise-resume property. A ``stop`` that a
        whole chunk would overshoot ends the iteration early (preemption
        semantics: partial-chunk work is lost anyway).
        """
        T = self.num_samples
        chunk = chunk_size if chunk_size > 0 else T
        stop = T if stop is None else min(stop, T)
        # per-step keys: pure function of the seed — identical every session
        collect_keys = jax.vmap(lambda k: jax.random.split(k, T))(
            carry["k_collect"]
        )
        while t_done < stop:
            t1 = min(t_done + chunk, T)
            if t1 > stop:
                break  # ragged chunk would shift later boundaries; stop here
            with span("sample.chunk"):
                count_steps(t1 - t_done, self.fused)
                state, theta_c, acc_c = self.chunk_fn(
                    self.shards,
                    self.counts,
                    carry["eps"],
                    carry["state"],
                    collect_keys[:, t_done:t1],
                )
                carry = {
                    "state": state,
                    "eps": carry["eps"],
                    "k_collect": carry["k_collect"],
                    "theta": jnp.concatenate([carry["theta"], theta_c], axis=1),
                    "accept_sum": carry["accept_sum"] + acc_c,
                }
                t0, t_done = t_done, t1
                # emitted chunks leave the backend's device layout (mesh
                # sharding must not leak into subscriber/combiner numerics)
                theta_l = self.backend.localize(theta_c)
                acc_l = self.backend.localize(acc_c)
                jax.block_until_ready(theta_l)  # honest landed_s: draws are real
            yield StreamChunk(
                theta_l, acc_l, t0, t1, T, carry,
                landed_s=time.monotonic(),
            )


class StreamedSample(NamedTuple):
    """Outcome of :func:`stream_sample`: the stage's result and how far it got."""

    result: SampleResult
    t_done: int
    total: int
    resumed_from: int  # 0 on a fresh run, else the restored draw count

    @property
    def complete(self) -> bool:
        return self.t_done >= self.total


def _restore_carry(checkpoint_dir, step, state_struct, d, num_shards):
    """Rebuild the carry pytree from a checkpoint, typed by the setup shapes."""
    state, eps, k_collect = state_struct
    template = {
        "state": state,
        "eps": eps,
        "k_collect": k_collect,
        "theta": jax.ShapeDtypeStruct((num_shards, step, d), jnp.float32),
        "accept_sum": jax.ShapeDtypeStruct((num_shards,), jnp.float32),
    }
    return restore(checkpoint_dir, step=step, template=template)


@span("sample.stage")
def stream_sample(
    key: jax.Array,
    model: BayesModel,
    data: PyTree,
    num_shards: int,
    num_samples: int,
    *,
    sampler: Optional[str] = None,
    warmup: int = 200,
    burn_in: int = 0,
    step_size: float = 0.1,
    sgld_batch: int = 256,
    sampler_options=(),
    shards: Optional[PyTree] = None,
    counts: Optional[jnp.ndarray] = None,
    chunk_size: int = 0,
    max_steps: Optional[int] = None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 0,
    spec_id: str = "",
    on_chunk: Sequence[Callable[[StreamChunk], None]] = (),
    mesh_shape: Optional[Tuple[int, int]] = None,
    check_hlo: bool = True,
) -> StreamedSample:
    """Run (or resume) the parallel sampling stage as one chunked stream.

    ``chunk_size`` is the emission cadence (0 ⇒ ``checkpoint_every``, else
    one T-sized chunk); ``on_chunk`` subscribers see every chunk *in order*,
    including — on a resumed run — the restored prefix re-emitted as
    ``replayed=True`` chunks at the original boundaries, so stateful
    subscribers (streaming combiners) rebuild exactly the uninterrupted
    trajectory. With ``checkpoint_dir`` the carry is persisted at every
    ``checkpoint_every`` boundary (which must be a multiple of the chunk
    cadence) and a later call resumes mid-chain bitwise; ``max_steps``
    bounds the draws collected this call (whole chunks only).

    ``mesh_shape`` with a data axis > 1 runs every chunk on the
    :class:`~repro.api.backends.MeshChunkBackend` — same streaming,
    checkpointing, and fused semantics, with each compiled program's HLO
    asserted collective-free across chain groups (``check_hlo=False`` skips
    the assert).

    The call is the ``sample.stage`` span (:mod:`repro.utils.spans`), each
    chunk a ``sample.chunk``; their ``steps`` count every chain's
    sequential transitions (warmup and burn-in included), their
    ``loglik_fused`` those whose gradient came from the fused pass.
    """
    chunk = chunk_size if chunk_size > 0 else checkpoint_every
    if checkpoint_every > 0 and chunk_size > 0 and checkpoint_every % chunk_size:
        raise ValueError(
            f"checkpoint_every={checkpoint_every} must be a multiple of the "
            f"stream chunk cadence {chunk_size} — saves land on chunk "
            "boundaries"
        )
    if max_steps is not None:
        if (
            checkpoint_dir is None
            or checkpoint_every <= 0
            or max_steps < checkpoint_every
        ):
            raise ValueError(
                f"max_steps={max_steps} cannot make durable progress: "
                "saves land on checkpoint boundaries, so it needs a "
                "checkpoint_dir, checkpoint_every > 0 and max_steps >= "
                f"checkpoint_every (got checkpoint_every={checkpoint_every})"
            )
    sampler = sampler or model.default_sampler
    if shards is None or counts is None:
        shards, counts = partition_data(
            data, num_shards, only=model.shard_keys, pad=True
        )
    padded = is_padded(model, shards, counts, sampler)
    stream = ShardChainStream(
        key,
        model,
        num_shards,
        num_samples,
        sampler=sampler,
        warmup=warmup,
        burn_in=burn_in,
        step_size=step_size,
        sgld_batch=sgld_batch,
        sampler_options=sampler_options,
        shards=shards,
        counts=counts,
        use_counts=padded,
        mesh_shape=mesh_shape,
        check_hlo=check_hlo,
    )

    # -- fused hot path: nobody subscribes, nothing to persist ---------------
    # (the 0 < chunk < T guard keeps the classic one-chunk program — and its
    # established numerics — for cadence-less runs)
    if (
        checkpoint_dir is None
        and not on_chunk
        and max_steps is None
        and 0 < chunk < num_samples
    ):
        count_steps(warmup + burn_in + num_samples, stream.fused)
        theta, accept_sum = stream.fused_sample(chunk)
        return StreamedSample(
            result=SampleResult(
                theta,
                accept_sum / jnp.maximum(num_samples, 1),
                counts,
                stream.backend.backend_id(FUSED),
                stream.backend.collectives_checked,
            ),
            t_done=num_samples,
            total=num_samples,
            resumed_from=0,
        )

    # -- restore or initialize ----------------------------------------------
    step = latest_step(checkpoint_dir) if checkpoint_dir is not None else None
    if step is not None:
        carry, meta = _restore_carry(
            checkpoint_dir, step, stream.setup_struct(), model.d, num_shards
        )
        # checkpoints restore as host arrays; the mesh backend re-commits
        # them to its devices (a no-op on the vmap backend)
        carry = stream.backend.put_carry(carry)
        if meta.get("spec_id") != spec_id or meta.get("T") != num_samples:
            raise ValueError(
                f"checkpoint at {checkpoint_dir} belongs to spec "
                f"{meta.get('spec_id')!r} (T={meta.get('T')}), not "
                f"{spec_id!r} (T={num_samples}) — refusing to resume"
            )
        t_done = int(meta["t_done"])
        # the bitwise guarantee rests on GLOBAL chunk boundaries; resuming an
        # unfinished run at a different cadence would replay the tail under a
        # different program split (a finished run has no tail to replay)
        if t_done < num_samples:
            if meta.get("checkpoint_every") != checkpoint_every:
                raise ValueError(
                    f"checkpoint at {checkpoint_dir} was written with "
                    f"checkpoint_every={meta.get('checkpoint_every')}; "
                    f"resuming mid-run with checkpoint_every="
                    f"{checkpoint_every} would shift chunk boundaries and "
                    "void the bitwise-resume guarantee — pass the original "
                    "cadence"
                )
            if meta.get("chunk", meta.get("checkpoint_every")) != chunk:
                raise ValueError(
                    f"checkpoint at {checkpoint_dir} streamed in chunks of "
                    f"{meta.get('chunk')}; resuming mid-run at cadence "
                    f"{chunk} would shift chunk boundaries and void the "
                    "bitwise-resume guarantee — pass the original cadence"
                )
        resumed_from = t_done
        # replay the restored prefix to subscribers at the original
        # boundaries so streaming-combiner state matches an uninterrupted run
        if on_chunk and t_done > 0:
            replay_chunk = chunk if chunk > 0 else num_samples
            zeros = jnp.zeros((num_shards,), jnp.float32)
            for r0 in range(0, t_done, replay_chunk):
                r1 = min(r0 + replay_chunk, t_done)
                ev = StreamChunk(
                    stream.backend.localize(carry["theta"][:, r0:r1]),
                    zeros, r0, r1, num_samples, carry, replayed=True,
                    landed_s=time.monotonic(),
                )
                for sub in on_chunk:
                    sub(ev)
    else:
        count_steps(warmup + burn_in, stream.fused)  # the setup's transitions
        carry = stream.fresh_carry()
        t_done = 0
        resumed_from = 0

    # -- the loop: chunks stream, everyone else subscribes -------------------
    stop = (
        num_samples if max_steps is None else min(num_samples, t_done + max_steps)
    )
    if stop < num_samples and checkpoint_every > 0:
        # a budgeted session must end on a SAVE boundary, not merely a chunk
        # boundary — chunks past the last checkpoint would be computed and
        # then silently lost (the work is only as durable as its last save)
        stop = (stop // checkpoint_every) * checkpoint_every
    for ev in stream.chunks(carry, t_done, chunk, stop):
        carry, t_done = ev.carry, ev.t1
        for sub in on_chunk:
            sub(ev)
        at_boundary = (
            checkpoint_every > 0 and t_done % checkpoint_every == 0
        ) or t_done == num_samples
        if checkpoint_dir is not None and at_boundary:
            save(
                checkpoint_dir,
                t_done,
                carry,
                metadata={
                    "spec_id": spec_id,
                    "t_done": t_done,
                    "T": num_samples,
                    "checkpoint_every": checkpoint_every,
                    "chunk": chunk,
                },
                keep=2,
            )

    accept = carry["accept_sum"] / jnp.maximum(t_done, 1)
    backend = stream.backend.backend_id(
        RESUMABLE if checkpoint_dir is not None else CHUNKED
    )
    return StreamedSample(
        result=SampleResult(
            carry["theta"], accept, counts, backend,
            stream.backend.collectives_checked,
        ),
        t_done=t_done,
        total=num_samples,
        resumed_from=resumed_from,
    )


# ---------------------------------------------------------------------------
# fused combine-fold (the P₁ program of the fused streaming hot path)
# ---------------------------------------------------------------------------


class FusedFold(NamedTuple):
    """Artifact of :func:`fused_fold`.

    ``states``: final in-scan state per combiner (feed through the face's
    ``to_state`` before the host ``finalize``). ``est_draws``: stacked
    ``(n_boundaries, n_estimate, d)`` in-scan trajectory draws for the
    combiners whose face ships a scan ``estimate``. ``boundaries``: the
    global draw indices the fold estimated at (full chunks + ragged tail).
    """

    states: Dict[str, Any]
    est_draws: Dict[str, jnp.ndarray]
    boundaries: Tuple[int, ...]


def fused_fold(
    theta: jnp.ndarray,
    faces: Dict[str, Any],  # name -> ScanStreamingFace, insertion-ordered
    est_keys: Dict[str, jnp.ndarray],  # name -> (n_boundaries,) stacked keys
    n_estimate: int,
    chunk: int,
    options: Dict[str, Any],
) -> FusedFold:
    """Fold the gathered draws through every scan face in ONE jitted program.

    A single ``lax.scan`` walks the ``(M, chunk, d)`` slices of ``theta`` (a
    reshape of the device-resident array — no host hop per chunk), folds each
    combiner's ``update`` and takes its in-scan ``estimate`` at every
    boundary; the fold states are donated into the program. The per-boundary
    estimate keys arrive pre-stacked so the trajectory RNG stream is exactly
    the subscriber path's (``fold_in(k_name, t1)``).

    Compiled programs are cached per (names, chunking, shapes, options) —
    scan faces resolve from the immutable in-process registry, so the name
    tuple pins the face closures exactly (same justification as the sampling
    executable cache).
    """
    M, T, d = theta.shape
    names = tuple(faces)
    est_names = tuple(n for n in names if n in est_keys)
    n_full, tail = divmod(T, chunk)
    boundaries = tuple(chunk * (i + 1) for i in range(n_full)) + (
        (T,) if tail else ()
    )
    key = (
        names, est_names, int(chunk), T, M, d, int(n_estimate),
        _freeze_options(options),
    )
    prog = _FUSED_FOLD_CACHE.get(key)
    if prog is None:
        from repro.utils.options import filter_kwargs

        upd = {n: faces[n].update for n in names}
        est_fns = {
            n: functools.partial(
                faces[n].estimate, **filter_kwargs(faces[n].estimate, options)
            )
            for n in est_names
        }

        def run(th, states, eks):
            body = th[:, : n_full * chunk]
            xs = jnp.moveaxis(body.reshape(M, n_full, chunk, d), 1, 0)
            eks_body = {n: eks[n][:n_full] for n in est_names}

            def step(ss, inp):
                th_c, ek = inp
                ss = {n: upd[n](ss[n], th_c) for n in names}
                ests = {
                    n: est_fns[n](ek[n], ss[n], n_estimate) for n in est_names
                }
                return ss, ests

            states, ests = jax.lax.scan(step, states, (xs, eks_body))
            if tail:
                th_t = th[:, n_full * chunk :]
                states = {n: upd[n](states[n], th_t) for n in names}
                ests = {
                    n: jnp.concatenate(
                        [ests[n], est_fns[n](eks[n][n_full], states[n], n_estimate)[None]]
                    )
                    for n in est_names
                }
            return states, ests

        prog = _FUSED_FOLD_CACHE[key] = jax.jit(run, donate_argnums=(1,))
    init_states = {n: faces[n].init(M, d) for n in names}
    states, ests = prog(theta, init_states, dict(est_keys))
    return FusedFold(states=states, est_draws=ests, boundaries=boundaries)
