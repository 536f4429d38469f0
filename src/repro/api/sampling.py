"""One subposterior chain, as every sampling driver of ``repro.api`` runs it.

The stage has three layers, one module each: this module runs one chain,
:mod:`repro.api.backends` places M chains on the devices, and
:mod:`repro.api.streaming` drives them.

- :func:`make_shard_kernel` packages one registry sampler for one model as a
  :class:`ShardKernel` — how to draw θ0, how to *build* the kernel from a
  concrete shard and a (possibly traced) step size, and how to project
  stacked positions back to the shared ``(T, d)`` θ.
- :func:`_setup_one` and :func:`_chunk_one` are the only code that runs one
  chain: the per-shard RNG discipline, init, warmup, burn-in, and then the
  collect of any number of draws from the live kernel state. The chunk
  backends vmap them over the chains (and ``shard_map`` them over a mesh);
  :func:`_whole_chain` composes them into one T-draw chain for the
  full-data :func:`groundtruth_chain` and the matrix runner
  (:mod:`repro.api.matrix`).
- :func:`sample_subposteriors` is the whole stage in one call: the chunk
  driver with one T-draw chunk and no subscriber.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.subposterior import make_subposterior_logpdf
from repro.models.bayes import BayesModel
from repro.samplers import filter_options, sampler_spec
from repro.samplers.adaptation import warmup_chain
from repro.samplers.base import MCMCKernel
from repro.utils.spans import count

PyTree = Any


class SampleResult(NamedTuple):
    """Output of the parallel sampling stage."""

    theta: jnp.ndarray  # (M, T, d) shared-θ subposterior draws
    accept: jnp.ndarray  # (M,) mean acceptance per chain
    counts: jnp.ndarray  # (M,) real data rows per shard (pad=True convention)
    backend: str  # a repro.api.backends.BackendId string
    collectives_checked: Optional[int]  # HLO collectives verified chain-local


def _identity(shard):
    return shard


class ShardKernel(NamedTuple):
    """One (model, sampler) pairing, ready to instantiate per shard.

    ``build(prepare(shard), count, step_size)`` must be pure and accept a
    traced ``step_size`` — every chunk rebuilds it from the chain's
    (possibly warmup-adapted) ε, a resumed stream from a checkpointed one,
    and the matrix runner from a runtime scalar. :func:`_setup_one` and
    :func:`_chunk_one` call ``prepare`` once per program, before their
    chain scans: it makes whatever the kernels read of the shard in a form
    of its own (the model's ``shard_form``), so no scan step makes it.
    """

    init_position: Callable[[jax.Array, PyTree], PyTree]
    build: Callable[[PyTree, jnp.ndarray, jnp.ndarray], MCMCKernel]
    extract: Callable[[PyTree], jnp.ndarray]  # stacked positions -> (T, d) θ
    adaptive: bool  # eligible for dual-averaging warmup
    target_accept: float
    prepare: Callable[[PyTree], PyTree] = _identity


def shard_rows(model: BayesModel, shards: PyTree, axis: int = 0) -> int:
    """Rows per shard: the per-datum leaves' ``axis`` (1 on stacked shards)."""
    per_datum = (
        shards if model.shard_keys is None
        else {k: shards[k] for k in model.shard_keys}
    )
    return jax.tree.leaves(per_datum)[0].shape[axis]


def loglik_fused(model: BayesModel, sampler: str, rows: int) -> bool:
    """Whether a chain's every transition takes its gradient from the model's
    fused likelihood pass: a gradient sampler, and a model whose
    ``fused_rows`` holds for shards of ``rows`` rows (for logistic
    regression: on a TPU, and at least ``MIN_KERNEL_N`` rows)."""
    return bool(
        sampler_spec(sampler).gradient
        and model.fused_rows is not None
        and model.fused_rows(rows)
    )


def count_steps(n: int, fused: bool) -> None:
    """Count ``n`` sequential transitions of every chain into the open span:
    ``steps``, and ``loglik_fused``, those whose gradient came from the fused
    likelihood pass."""
    count("steps", n)
    count("loglik_fused", n if fused else 0)


def _shard_axes(shards: PyTree, shard_keys, per_datum_leaf, broadcast_leaf):
    """Per-leaf vmap axes / PartitionSpecs: per-datum leaves carry the chain
    axis, broadcast leaves (e.g. gmm mixture weights) are replicated."""
    if shard_keys is None:
        return jax.tree.map(lambda _: per_datum_leaf, shards)
    return {
        k: (per_datum_leaf if k in shard_keys else broadcast_leaf)
        for k in shards
    }


def make_shard_kernel(
    model: BayesModel,
    num_shards: int,
    sampler: str,
    *,
    sgld_batch: int = 256,
    use_counts: bool = True,
    sampler_options=(),
) -> ShardKernel:
    """Package one registry sampler for one model as a :class:`ShardKernel`.

    ``use_counts=False`` statically drops the padded-row likelihood
    correction (every shard row is real) so the divisible-N hot path pays
    nothing for pad support. ``sampler_options`` (e.g. RunSpec's field) is
    filtered per factory signature — the registry's option-forwarding
    convention — and splatted into every kernel build; keys this layer owns
    (the logpdf wiring, step size, Gibbs blocks, SGLD closures) are
    reserved and dropped.
    """
    spec = sampler_spec(sampler)
    _RESERVED = ("step_size", "block_updates", "grad_logpdf", "batch_fn")
    extra = {
        k: v
        for k, v in filter_options(spec.factory, dict(sampler_options)).items()
        if k not in _RESERVED
    }

    if spec.name == "gibbs":  # alias-safe: spec.name is canonical
        if not model.has_gibbs:
            raise ValueError(
                f"model {model.name!r} supplies no Gibbs blocks "
                "(BayesModel.gibbs_blocks)"
            )
        # models declaring gibbs_counts mask the edge-padded replicated rows
        # out of their conditionals (count= is the pad convention's valid
        # prefix); everyone else sees the raw shard, exactly as before
        pass_count = model.gibbs_counts and use_counts

        def build_gibbs(shard, count, step_size):
            kwargs = {"count": count} if pass_count else {}
            blocks = model.gibbs_blocks(
                shard, num_shards, step_size=step_size, **kwargs
            )
            return spec.factory(
                None, step_size=step_size, block_updates=blocks, **extra
            )

        return ShardKernel(
            init_position=lambda k, shard: model.gibbs_init(k, shard),
            build=build_gibbs,
            extract=model.gibbs_extract,
            adaptive=False,
            target_accept=spec.target_accept,
        )

    def make_logpdf(shard, count, form=None):
        # no form, no keyword: the raw rows' call stays the one that a
        # wrapper written to the older signature also serves
        return make_subposterior_logpdf(
            model.log_prior,
            model.log_lik,
            shard,
            num_shards,
            count=count if use_counts else None,
            per_datum=model.shard_keys,
            **({} if form is None else {"form": form}),
        )

    if spec.name == "sgld":

        def build_sgld(shard, count, step_size):
            # minibatch subposterior gradients (paper §7): scale by the
            # shard's REAL row count so padded rows never bias the estimate
            if model.shard_keys is None:
                per_datum = shard
                rest = None
            else:
                per_datum = {k: shard[k] for k in model.shard_keys}
                rest = {k: v for k, v in shard.items() if k not in model.shard_keys}
            shard_size = jax.tree.leaves(per_datum)[0].shape[0]
            batch_size = min(sgld_batch or shard_size, shard_size)
            inv_m = 1.0 / float(num_shards)
            n_real = count if use_counts else shard_size

            def mb_logpdf(theta, batch):
                scale = jnp.asarray(n_real, jnp.float32) / float(batch_size)
                return inv_m * model.log_prior(theta) + scale * model.log_lik(
                    theta, batch
                )

            def batch_fn(k, _t):
                idx = jax.random.randint(
                    k, (batch_size,), 0, jnp.maximum(n_real, 1)
                )
                batch = jax.tree.map(lambda x: x[idx], per_datum)
                return batch if rest is None else {**rest, **batch}

            return spec.factory(
                make_logpdf(shard, count),
                step_size=step_size,
                grad_logpdf=jax.grad(mb_logpdf),
                batch_fn=batch_fn,
                **extra,
            )

        return ShardKernel(
            init_position=model.initial_position,
            build=build_sgld,
            extract=lambda pos: pos,
            adaptive=False,
            target_accept=spec.target_accept,
        )

    def prepare_mh(shard):
        fused = loglik_fused(model, spec.name, shard_rows(model, shard))
        return shard, (model.shard_form(shard) if fused else None)

    def build_mh(prepared, count, step_size):
        shard, form = prepared
        return spec.factory(
            make_logpdf(shard, count, form), step_size=step_size, **extra
        )

    return ShardKernel(
        init_position=model.initial_position,
        build=build_mh,
        extract=lambda pos: pos,
        adaptive=spec.adaptive,
        target_accept=spec.target_accept,
        prepare=prepare_mh,
    )


def _setup_one(sk: ShardKernel, shard, count, key, *, burn_in, warmup, step_size):
    """Init, warmup and burn-in of one chain: ``(state, eps, k_collect)``.

    The per-shard RNG discipline: ``k_init, k_run = split(key)``; adaptive
    kernels split ``k_warm`` off ``k_run`` and spend ``warmup``
    dual-averaging transitions, non-adaptive ones treat them as extra
    burn-in (registry convention); burn-in steps on ``split(k_run, burn +
    1)[1:]`` and leaves the first key to collect with.
    """
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


def _whole_chain(sk: ShardKernel, shard, count, key, *, num_samples, burn_in,
                 warmup, step_size):
    """One chain of ``num_samples`` kept draws: ``(theta (T, d),
    mean_accept)``. The setup, then one collect on ``split(k_collect, T)``
    — the keys the chunk drivers hand out, chunk by chunk."""
    state, eps, k_collect = _setup_one(
        sk, shard, count, key,
        burn_in=burn_in, warmup=warmup, step_size=step_size,
    )
    _, theta, acc = _chunk_one(
        sk, shard, count, eps, state, jax.random.split(k_collect, num_samples)
    )
    return theta, acc / max(num_samples, 1)


def sample_subposteriors(
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
    check_hlo: bool = True,
    mesh_shape: Optional[Tuple[int, int]] = None,
    sampler_options=(),
    shards: Optional[PyTree] = None,
    counts: Optional[jnp.ndarray] = None,
) -> SampleResult:
    """The embarrassingly parallel stage: M independent subposterior chains.

    :func:`repro.api.streaming.stream_sample` with one T-draw chunk and no
    subscriber, on the mesh :func:`repro.api.backends.auto_mesh_shape`
    picks: ``mesh_shape``, else a ``(ndev, 1)`` mesh when more than one
    device is visible and ``num_shards`` divides over them, else the vmap
    backend. Partitions ``data`` unless the caller passes ``shards`` and
    ``counts``. On a mesh every compiled program's HLO is asserted
    collective-free across chains (``check_hlo=False`` skips the assert).
    """
    # late imports: both modules import this one
    from repro.api.backends import auto_mesh_shape
    from repro.api.streaming import stream_sample

    return stream_sample(
        key,
        model,
        data,
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
        mesh_shape=auto_mesh_shape(mesh_shape, num_shards),
        check_hlo=check_hlo,
    ).result


def is_padded(model, shards, counts, sampler) -> bool:
    """Whether any shard carries replicated pad rows (and guard gibbs)."""
    rows = shard_rows(model, shards, axis=1)
    padded = bool(jax.device_get(jnp.any(counts != rows)))
    if (
        padded
        and sampler_spec(sampler).name == "gibbs"
        and not model.gibbs_counts
    ):
        raise ValueError(
            f"model {model.name!r}'s gibbs block updates operate on the raw "
            "shard and cannot mask padded rows (BayesModel.gibbs_counts is "
            "False); choose M dividing N "
            f"(counts={jax.device_get(counts)})"
        )
    return padded


def chain_mesh(shape: Tuple[int, ...], axis_names: Tuple[str, ...]):
    """A device mesh for the chain programs, with ``Auto`` axes.

    The chain programs leave sharding to GSPMD propagation: their outputs
    flow on into unsharded combine code. ``jax.make_mesh`` defaults to
    ``Explicit`` axes, under which that code refuses the sharded draws.
    """
    from jax.sharding import AxisType

    return jax.make_mesh(
        tuple(shape), axis_names, axis_types=(AxisType.Auto,) * len(axis_names)
    )


def _freeze_options(options) -> Tuple:
    """A hashable form of an options mapping (or pair sequence), for cache keys."""
    items = options.items() if hasattr(options, "items") else options
    return tuple(sorted((str(k), v) for k, v in items))


# full-data chain programs, one per signature
_GROUNDTRUTH_CACHE: Dict[Tuple, Callable] = {}


def groundtruth_chain(
    key: jax.Array,
    model: BayesModel,
    data: PyTree,
    num_samples: int,
    *,
    sampler: Optional[str] = None,
    warmup: int = 200,
    burn_in: int = 0,
    step_size: float = 0.1,
    sgld_batch: int = 256,
    sampler_options=(),
) -> jnp.ndarray:
    """Single full-data chain (num_shards=1) with the same sampler surface:
    :func:`_whole_chain` in one program, obtained once per signature."""
    sampler = sampler or model.default_sampler
    sig = (
        model.name, sampler, num_samples, warmup, burn_in, float(step_size),
        sgld_batch, _freeze_options(sampler_options),
    )
    run = _GROUNDTRUTH_CACHE.get(sig)
    if run is None:
        sk = make_shard_kernel(
            model, 1, sampler,
            sgld_batch=sgld_batch,
            use_counts=False,  # full data: every row is real
            sampler_options=sampler_options,
        )

        def chain(full, k):
            theta, _ = _whole_chain(
                sk, full, jnp.zeros((), jnp.int32), k,
                num_samples=num_samples, burn_in=burn_in, warmup=warmup,
                step_size=step_size,
            )
            return theta

        run = _GROUNDTRUTH_CACHE[sig] = jax.jit(chain)
    return run(data, key)
