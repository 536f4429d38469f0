"""Any-MCMC substrate (paper criterion 3: each machine may use any sampler).

Registry convention
-------------------
Samplers live behind a name registry, mirroring ``repro.core.combiners``:
implementations self-register with ``@register_sampler("name")`` and share one
uniform factory signature

    factory(logpdf, *, step_size, **options) -> MCMCKernel

so every consumer (the ``mcmc_run`` pipeline's ``--sampler`` flag, benchmarks,
conformance tests) resolves kernels with ``get_sampler(name)`` and forwards a
single option dict filtered per factory signature (``filter_options`` —
``**_ignored`` marks tolerated-but-unused keywords). Built-ins: ``rwmh``,
``mala``, ``hmc``, ``gibbs`` (Metropolis-within-Gibbs over model-supplied
block updates) and ``sgld`` (minibatch Langevin over
``make_minibatch_logpdf`` gradients). Adding a sampler here makes it
reachable from every consumer at once.

Warmup convention
-----------------
Registered samplers carry a ``SamplerSpec(adaptive, target_accept, gradient)``.
Adaptive kernels are warmed up by dual averaging: pass a *factory*
``step_size -> MCMCKernel`` plus ``warmup=n`` to ``run_chain`` and the step
size adapts toward ``target_accept`` per chain under ``lax.scan`` (vmap- and
shard_map-compatible; see :mod:`repro.samplers.adaptation`) — hand-tuned
per-model step constants are dead. Non-adaptive samplers (``gibbs``,
``sgld``) treat warmup steps as extra burn-in.

All kernels share the ``(init, step)`` protocol of :mod:`repro.samplers.base`
and are pytree-generic; chains are driven by :func:`repro.samplers.base.run_chain`
(jit/scan) and batched with :func:`repro.samplers.base.run_chains` (vmap).
"""

from repro.samplers import base as base  # noqa: F401
from repro.samplers.adaptation import (  # noqa: F401
    DualAveragingState,
    da_init,
    da_update,
    warmup_chain,
)
from repro.samplers.base import run_chain, run_chains  # noqa: F401
from repro.samplers.gibbs import gibbs_kernel, mh_within_gibbs_update  # noqa: F401
from repro.samplers.hmc import hmc_kernel, window_adaptation  # noqa: F401
from repro.samplers.mala import mala_kernel  # noqa: F401
from repro.samplers.registry import (  # noqa: F401
    SamplerSpec,
    available_samplers,
    canonical_samplers,
    filter_options,
    get_sampler,
    register_sampler,
    sampler_spec,
)
from repro.samplers.rwmh import rwmh_kernel  # noqa: F401
from repro.samplers.sgld import sgld_kernel  # noqa: F401
