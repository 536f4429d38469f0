"""Sampler registry: every MCMC kernel behind one uniform factory signature.

A *sampler factory* is any callable

    factory(logpdf, *, step_size, **options) -> MCMCKernel

decorated with :func:`register_sampler`. Consumers (the ``mcmc_run`` pipeline,
benchmarks, conformance tests) resolve samplers by name with
:func:`get_sampler` and enumerate them with :func:`available_samplers` —
exactly the architecture of ``repro.core.combiners``: adding a sampler here
makes it reachable from every consumer at once, including the CLI's
``--sampler`` flag.

Each registration carries metadata in a :class:`SamplerSpec`:

- ``adaptive``: whether the kernel's acceptance probability responds to
  ``step_size`` — adaptive samplers are eligible for the dual-averaging
  warmup phase (``run_chain(..., warmup=n)`` with a ``step_size -> kernel``
  factory); non-adaptive ones (Gibbs always accepts, SGLD never rejects)
  treat warmup steps as extra burn-in.
- ``target_accept``: the warmup's target acceptance rate (sampler-specific
  optima: ~0.35 for random-walk MH, ~0.55 for MALA, 0.8 for HMC).
- ``gradient``: whether every transition takes the log-density's value and
  gradient together (MALA, HMC), so a model's fused likelihood pass
  (``BayesModel.fused_rows``) can serve it.

Option-forwarding follows the combiners' convention: callers broadcasting one
option dict over many samplers filter it per factory signature with
:func:`filter_options`; ``**_ignored`` in a factory marks tolerated-but-unused
keywords, which are dropped here rather than silently swallowed there.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.utils.options import filter_kwargs

from repro.samplers.base import (
    LogDensityFn,
    MCMCKernel,
    PyTree,
    StepInfo,
)
from repro.samplers.gibbs import BlockUpdate, gibbs_kernel
from repro.samplers.hmc import hmc_kernel
from repro.samplers.mala import mala_kernel
from repro.samplers.rwmh import rwmh_kernel
from repro.samplers.sgld import sgld_kernel

SamplerFactory = Callable[..., MCMCKernel]


class SamplerSpec(NamedTuple):
    """Registry entry: factory + the metadata the warmup phase needs."""

    name: str
    factory: SamplerFactory
    adaptive: bool
    target_accept: float
    gradient: bool


_REGISTRY: Dict[str, SamplerSpec] = {}
_CANONICAL: Dict[str, SamplerSpec] = {}  # primary names only (no aliases)


def register_sampler(
    name: str,
    *aliases: str,
    adaptive: bool = True,
    target_accept: float = 0.8,
    gradient: bool = False,
) -> Callable[[SamplerFactory], SamplerFactory]:
    """Decorator: add a sampler factory to the registry under ``name``."""

    def deco(fn: SamplerFactory) -> SamplerFactory:
        spec = SamplerSpec(
            name=name, factory=fn, adaptive=adaptive,
            target_accept=target_accept, gradient=gradient,
        )
        for key in (name, *aliases):
            if key in _REGISTRY:
                raise ValueError(f"sampler {key!r} already registered")
            _REGISTRY[key] = spec
        _CANONICAL[name] = spec
        return fn

    return deco


def sampler_spec(name: str) -> SamplerSpec:
    """Resolve the full registry entry (raises KeyError with choices)."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown sampler {name!r}; available: {', '.join(available_samplers())}"
        ) from None


def get_sampler(name: str) -> SamplerFactory:
    """Resolve a sampler factory by registry name."""
    return sampler_spec(name).factory


def available_samplers() -> Tuple[str, ...]:
    """All registered sampler names (aliases included), sorted."""
    return tuple(sorted(_REGISTRY))


def canonical_samplers() -> Tuple[str, ...]:
    """Primary registration names only (aliases dropped), sorted."""
    return tuple(sorted(_CANONICAL))


def filter_options(factory: SamplerFactory, options: Dict[str, Any]) -> Dict[str, Any]:
    """Keep only the keyword options the factory's signature declares.

    Same convention as ``repro.core.combiners.filter_options`` — both
    delegate to :func:`repro.utils.options.filter_kwargs`: ``**options`` (no
    underscore) marks a passthrough wrapper that receives everything;
    ``**_ignored`` marks tolerated-but-unused keywords, dropped here.
    """
    return filter_kwargs(factory, options)


# ---------------------------------------------------------------------------
# built-in registrations
# ---------------------------------------------------------------------------


@register_sampler("rwmh", "mh", target_accept=0.35)
def rwmh(
    logpdf: LogDensityFn,
    *,
    step_size: float | jnp.ndarray = 0.1,
    proposal_fn: Optional[Callable[[jax.Array, PyTree], PyTree]] = None,
    **_ignored,
) -> MCMCKernel:
    """Random-walk Metropolis–Hastings (paper §2's example sampler)."""
    return rwmh_kernel(logpdf, step_size=step_size, proposal_fn=proposal_fn)


@register_sampler("mala", target_accept=0.55, gradient=True)
def mala(
    logpdf: LogDensityFn, *, step_size: float | jnp.ndarray = 0.05, **_ignored
) -> MCMCKernel:
    """Metropolis-adjusted Langevin."""
    return mala_kernel(logpdf, step_size=step_size)


@register_sampler("hmc", target_accept=0.8, gradient=True)
def hmc(
    logpdf: LogDensityFn,
    *,
    step_size: float | jnp.ndarray = 0.1,
    num_integration_steps: int = 10,
    inv_mass: Optional[PyTree] = None,
    **_ignored,
) -> MCMCKernel:
    """Fixed-length HMC with jittered trajectory length."""
    return hmc_kernel(
        logpdf,
        step_size=step_size,
        num_integration_steps=num_integration_steps,
        inv_mass=inv_mass,
    )


@register_sampler("gibbs", "metropolis_within_gibbs", adaptive=False)
def gibbs(
    logpdf: Optional[LogDensityFn],
    *,
    step_size: float = 0.1,
    block_updates: Sequence[BlockUpdate] = (),
    **_ignored,
) -> MCMCKernel:
    """(Metropolis-within-)Gibbs over model-supplied block updates.

    The blocks come from the model (``BayesModel.gibbs_blocks`` builds them
    against a concrete data shard — e.g. the Poisson–gamma conjugate
    ``q_i | a,b,x`` updates of paper §8.3); ``step_size`` is the scale the
    model used for its MH-within-Gibbs blocks and is accepted here only for
    signature uniformity. ``logpdf`` may be ``None``: Gibbs positions are
    often extended pytrees (shard-local latents) the flat-θ log-density
    cannot score, and the kernel only uses it for diagnostics.
    """
    if not block_updates:
        raise ValueError(
            "gibbs requires model-supplied block_updates "
            "(see BayesModel.gibbs_blocks)"
        )
    return gibbs_kernel(list(block_updates), logdensity=logpdf)


@register_sampler("sgld", adaptive=False)
def sgld(
    logpdf: Optional[LogDensityFn],
    *,
    step_size: float | Callable[[jnp.ndarray], jnp.ndarray] = 1e-3,
    grad_logpdf: Optional[Callable[[PyTree, Any], PyTree]] = None,
    batch_fn: Optional[Callable[[jax.Array, jnp.ndarray], Any]] = None,
    preconditioner: Optional[str] = None,
    temperature: float = 1.0,
    **_ignored,
) -> MCMCKernel:
    """SGLD adapted to the uniform ``(init, step)`` protocol.

    Minibatch mode (paper §7: stochastic-gradient subposterior sampling):
    ``grad_logpdf(theta, batch)`` is the minibatch gradient — e.g.
    ``jax.grad`` of :func:`repro.core.subposterior.make_minibatch_logpdf` —
    and ``batch_fn(key, t)`` draws the batch for step ``t``. With both left
    ``None`` the kernel degrades to full-gradient (unadjusted) Langevin on
    ``logpdf``. No MH correction ⇒ reported ``accept_prob`` is 1 and the
    sampler is non-adaptive (discretization bias is controlled by
    ``step_size``, not an acceptance rate).
    """
    if grad_logpdf is None:
        if logpdf is None:
            raise ValueError("sgld needs logpdf or an explicit grad_logpdf")
        full_grad = jax.grad(logpdf)
        grad_logpdf = lambda theta, _batch: full_grad(theta)
    base = sgld_kernel(
        grad_logpdf,
        step_size=step_size,
        preconditioner=preconditioner,
        temperature=temperature,
    )

    def init(position: PyTree):
        return base.init(position)

    def step(key: jax.Array, state):
        if batch_fn is None:
            batch, k_step = None, key
        else:
            k_batch, k_step = jax.random.split(key)
            batch = batch_fn(k_batch, state.step)
        state, _gnorm = base.step(k_step, state, batch)
        info = StepInfo(
            accept_prob=jnp.ones(()),
            is_accepted=jnp.ones((), bool),
            log_density=jnp.zeros(()),
        )
        return state, info

    return MCMCKernel(init=init, step=step)
