"""The embarrassingly parallel sampling stage behind ``repro.api``.

Moved here from ``repro.launch.mcmc_run`` (which now only adapts argparse
flags onto a :class:`repro.api.RunSpec`) and factored into two layers:

- :func:`make_shard_kernel` packages one registry sampler for one model as a
  :class:`ShardKernel` — how to draw θ0, how to *build* the kernel from a
  concrete shard and a (possibly traced) step size, and how to project
  stacked positions back to the shared ``(T, d)`` θ. Because ``build`` is a
  pure function of ``(shard, count, step_size)``, the same ShardKernel
  serves three drivers: the one-shot chain here, the chunk-emitting stream
  driver (:mod:`repro.api.streaming` — checkpointing and combine-while-
  sampling subscribe to it; it rebuilds the kernel from a checkpointed ε on
  resume), and the compile-cached matrix runner (:mod:`repro.api.matrix`,
  which traces ``step_size`` so specs differing only there share one
  executable).
- :func:`run_shard_chain` is the per-shard glue — RNG discipline, warmup
  dispatch, burn-in accounting — shared by every driver so their draws are
  bitwise identical.

The public entry points keep their historical signatures:
:func:`make_shard_sampler`, :func:`sample_subposteriors` (vmap on one
device, ``shard_map`` over the mesh ``data`` axis with the compiled HLO
asserted collective-free given more), and :func:`groundtruth_chain`.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.subposterior import make_subposterior_logpdf, partition_data
from repro.models.bayes import BayesModel
from repro.samplers import filter_options, run_chain, sampler_spec
from repro.samplers.base import MCMCKernel
from repro.utils.spans import count, span

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
    traced ``step_size`` — the resumable stream re-invokes it from a
    checkpointed (possibly warmup-adapted) ε, and the matrix runner from a
    runtime scalar. ``run_shard_chain`` and the chunk backends call
    ``prepare`` once per program, before their chain scans: it makes
    whatever the kernels read of the shard in a form of its own (the
    model's ``shard_form``), so no scan step makes it.
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


def run_shard_chain(
    sk: ShardKernel,
    shard: PyTree,
    count: jnp.ndarray,
    key: jax.Array,
    *,
    num_samples: int,
    burn_in: int,
    warmup: int,
    step_size: float | jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One subposterior chain: ``(theta (T, d), mean_accept)``.

    The single source of the per-shard RNG discipline (``k_init, k_run =
    split(key)``) and of the warmup/burn-in accounting: adaptive kernels
    spend ``warmup`` dual-averaging transitions, non-adaptive ones treat
    them as extra burn-in (registry convention).
    """
    k_init, k_run = jax.random.split(key)
    pos0 = sk.init_position(k_init, shard)
    prepared = sk.prepare(shard)
    if sk.adaptive and warmup > 0:
        pos, info = run_chain(
            k_run,
            lambda eps: sk.build(prepared, count, eps),
            pos0,
            num_samples,
            burn_in=burn_in,
            warmup=warmup,
            initial_step_size=step_size,
            target_accept=sk.target_accept,
        )
    else:
        kern = sk.build(prepared, count, step_size)
        pos, info = run_chain(
            k_run,
            kern,
            pos0,
            num_samples,
            burn_in=burn_in + (0 if sk.adaptive else warmup),
        )
    return sk.extract(pos), info.is_accepted.mean()


def make_shard_sampler(
    model: BayesModel,
    num_shards: int,
    sampler: str,
    *,
    num_samples: int,
    burn_in: int,
    warmup: int,
    step_size: float,
    sgld_batch: int = 256,
    use_counts: bool = True,
    sampler_options=(),
) -> Callable[[PyTree, jnp.ndarray, jax.Array], Tuple[jnp.ndarray, jnp.ndarray]]:
    """Build ``one_shard(shard, count, key) -> (theta (T, d), mean_accept)``.

    The returned function is pure and shape-uniform across shards, so the
    launch layer can drive it under ``vmap`` (one device) or ``shard_map``
    (chain groups over the mesh data axis) unchanged.
    """
    sk = make_shard_kernel(
        model,
        num_shards,
        sampler,
        sgld_batch=sgld_batch,
        use_counts=use_counts,
        sampler_options=sampler_options,
    )

    def one_shard(shard, count, key):
        return run_shard_chain(
            sk,
            shard,
            count,
            key,
            num_samples=num_samples,
            burn_in=burn_in,
            warmup=warmup,
            step_size=step_size,
        )

    return one_shard


@span("sample.stage")
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

    Partitions ``data`` (edge-padded — non-divisible N is fine), then runs
    one chain per shard; a caller that already partitioned (e.g.
    ``Pipeline.partition()``'s artifact) passes ``shards``/``counts`` to
    skip the duplicate copy. With >1 local device and ``num_shards``
    divisible by the device count, chains are ``shard_map``-ped over the
    ``data`` axis of a ``(ndev, 1)`` ("data", "model") mesh (override via
    ``mesh_shape``) and the compiled HLO is asserted collective-free across
    chains; otherwise the chains are vmapped on one device. Zero cross-chain
    communication either way. The call is the ``sample.stage`` span
    (:mod:`repro.utils.spans`); its ``steps`` count each chain's transitions,
    its ``loglik_fused`` those whose gradient came from the fused pass.
    """
    sampler = sampler or model.default_sampler
    if shards is None or counts is None:
        shards, counts = partition_data(
            data, num_shards, only=model.shard_keys, pad=True
        )
    padded = is_padded(model, shards, counts, sampler)
    one_shard = make_shard_sampler(
        model,
        num_shards,
        sampler,
        num_samples=num_samples,
        burn_in=burn_in,
        warmup=warmup,
        step_size=step_size,
        sgld_batch=sgld_batch,
        # divisible N ⇒ every row is real ⇒ skip the pad correction entirely
        use_counts=padded,
        sampler_options=sampler_options,
    )
    count_steps(
        warmup + burn_in + num_samples,
        loglik_fused(model, sampler, shard_rows(model, shards, axis=1)),
    )
    keys = jax.random.split(key, num_shards)
    in_axes = (_shard_axes(shards, model.shard_keys, 0, None), 0, 0)
    vmapped = jax.vmap(one_shard, in_axes=in_axes)

    # late import: backends imports this module (kernel layer) at load time
    from repro.api.backends import BackendId

    ndev = jax.device_count()
    if mesh_shape is None and ndev > 1 and num_shards % ndev == 0:
        mesh_shape = (ndev, 1)
    if mesh_shape is not None and mesh_shape[0] > 1:
        theta, acc, checked = _sample_on_mesh(
            vmapped, shards, counts, keys, model, mesh_shape, check_hlo
        )
        return SampleResult(
            theta, acc, counts, BackendId.mesh(mesh_shape[0]), checked
        )
    theta, acc = jax.jit(vmapped)(shards, counts, keys)
    return SampleResult(theta, acc, counts, BackendId.vmap(), None)


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


def _sample_on_mesh(vmapped, shards, counts, keys, model, mesh_shape, check_hlo):
    """shard_map the vmapped per-shard sampler over the mesh data axis.

    Each device owns ``M/ndev`` chains + their data shards; broadcast leaves
    are replicated. The jitted program is lowered AOT so the post-SPMD HLO
    can be asserted collective-free *before* it runs — the machine-checked
    "embarrassingly parallel" property.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    # late import: epmcmc pulls the (heavy) LM stack this path otherwise skips
    from repro.distributed.epmcmc import assert_no_cross_chain_collectives

    mesh = chain_mesh(mesh_shape, ("data", "model"))
    shard_specs = _shard_axes(shards, model.shard_keys, P("data"), P())
    body = jax.shard_map(
        vmapped,
        mesh=mesh,
        in_specs=(shard_specs, P("data"), P("data")),
        out_specs=(P("data"), P("data")),
        check_vma=False,
    )
    compiled = jax.jit(body).lower(shards, counts, keys).compile()
    checked = None
    if check_hlo:
        checked = assert_no_cross_chain_collectives(compiled.as_text(), mesh)
    put = lambda tree, specs: jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs
    )
    theta, acc = compiled(
        put(shards, shard_specs), put(counts, P("data")), put(keys, P("data"))
    )
    return theta, acc, checked


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
    """Single full-data chain (num_shards=1) with the same sampler surface."""
    one = make_shard_sampler(
        model,
        1,
        sampler or model.default_sampler,
        num_samples=num_samples,
        burn_in=burn_in,
        warmup=warmup,
        step_size=step_size,
        sgld_batch=sgld_batch,
        use_counts=False,  # full data: every row is real
        sampler_options=sampler_options,
    )
    theta, _ = jax.jit(lambda k: one(data, jnp.zeros((), jnp.int32), k))(key)
    return theta
