"""EP-MCMC on the mesh — the paper's algorithm as a first-class training mode.

The mapping (DESIGN.md §3): the mesh's ``data`` axis (× ``pod`` on multi-pod)
hosts **M independent subposterior chains**. Chain c owns

- its own parameter state θ_c (pytree stacked on a leading chain axis,
  sharded ``P('data', <TP spec>)``),
- a disjoint data shard (the paper's partition),
- an independent RNG stream.

The SGLD transition on chain c targets the subposterior (paper Eq. 2.1)

    log p_c(θ) = (1/M)·log p(θ) + (N_c/B)·Σ_{i∈batch} log p(x_i|θ)

Because the chain axis is *vmapped* (no op ever mixes chains), GSPMD lowers
the whole sampling step with **zero collectives across the data/pod axes** —
the paper's "embarrassingly parallel" claim, checkable in the HLO
(:func:`assert_no_cross_chain_collectives`, exercised by tests and the
dry-run). The ``model`` axis still carries ordinary TP collectives *within*
a chain. Compare ``--mode sgd``: identical step, but gradients are averaged
over chains (psum over data axes) every step — the communication the paper
deletes.

Combination (§3) communicates once at the end:
- parametric, full θ (BvM regime): per-chain diagonal running moments →
  ``product_moments_diag`` over the chain axis — a single O(d) reduce.
- exact combiners (nonparametric/semiparametric IMG): run on a designated
  low-dim parameter *subset* (or summary) — all-gather of (M, T, d_sub),
  then :func:`combine_gathered` resolves the strategy by registry name
  (``repro.core.combiners``).
"""

from __future__ import annotations

import functools
import re
from typing import Any, Callable, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.api.hlo import (  # noqa: F401  (the HLO assert's older home)
    _iota_groups,
    assert_no_cross_chain_collectives,
    collective_groups,
)
from repro.core.gaussian import GaussianMoments, product_moments_diag
from repro.distributed import sharding as shd
from repro.models.lm import model as mdl
from repro.models.lm import steps
from repro.models.lm.config import ModelConfig

PyTree = Any

PRIOR_SIGMA = 1.0  # N(0, σ²) prior over every weight — BvM-regime reference prior


class EpmcmcState(NamedTuple):
    """State of M parallel subposterior SGLD chains (+ streaming moments)."""

    params: PyTree  # (C, ...) stacked chain parameters
    v: PyTree  # (C, ...) RMSProp preconditioner accumulators
    step: jnp.ndarray  # () int32
    key: jax.Array  # (C, 2) per-chain RNG
    # streaming diagonal moments of the post-burn-in samples, per chain:
    m_count: jnp.ndarray  # (C,)
    m_mean: PyTree  # (C, ...) running mean of θ samples
    m_var: PyTree  # (C, ...) running Σ(θ−mean)² (Welford)


def num_chains(mesh: Mesh) -> int:
    return int(
        mesh.shape.get("pod", 1) * mesh.shape.get("data", 1)
        if "data" in mesh.shape
        else 1
    )


def init_state(key: jax.Array, cfg: ModelConfig, n_chains: int) -> EpmcmcState:
    """vmapped per-chain init — every chain starts at a different draw
    (overdispersed starts parallelize burn-in diagnostics)."""
    keys = jax.random.split(key, n_chains)
    params = jax.vmap(lambda k: mdl.init_params(k, cfg))(keys)
    zeros_like_f32 = lambda t: jax.tree.map(
        lambda x: jnp.zeros(x.shape, jnp.float32), t
    )
    return EpmcmcState(
        params=params,
        v=zeros_like_f32(params),
        step=jnp.zeros((), jnp.int32),
        key=jax.vmap(jax.random.fold_in)(keys, jnp.arange(n_chains)),
        m_count=jnp.zeros((n_chains,), jnp.float32),
        m_mean=zeros_like_f32(params),
        m_var=zeros_like_f32(params),
    )


# ---------------------------------------------------------------------------
# sharding
# ---------------------------------------------------------------------------


def chain_axes(mesh: Mesh) -> Tuple[str, ...]:
    return shd.batch_axes(mesh)  # ('pod','data') / ('data',)


def _prepend_chain_axis(spec: P, axes: Tuple[str, ...]) -> P:
    return P(axes, *tuple(spec))


def state_specs(cfg: ModelConfig, mesh: Mesh, state: EpmcmcState) -> EpmcmcState:
    """PartitionSpecs: chain axis over data(/pod); TP spec per chain inside.

    Reuses :func:`repro.distributed.sharding.param_spec` — stacked leaves have
    one extra leading dim, which the path rules emit as a leading ``None``;
    we overwrite it with the chain axes. FSDP is force-disabled: the data
    axis belongs to the chains (each chain's state is TP-sharded only —
    ZeRO-style sharding would put 'data' on a second dim of the same leaf).
    """
    import dataclasses as _dc

    ca = chain_axes(mesh)
    cfg_tp = _dc.replace(cfg, fsdp=False)

    def stacked_specs(tree: PyTree) -> PyTree:
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        out = []
        for path, leaf in flat:
            # spec of the UNSTACKED per-chain leaf (path rules are written
            # against per-chain shapes), then prepend the chain axis
            unstacked = jax.ShapeDtypeStruct(leaf.shape[1:], leaf.dtype)
            spec = shd.param_spec(cfg_tp, mesh, shd._path_str(path), unstacked)
            parts = list(spec) + [None] * (len(unstacked.shape) - len(spec))
            out.append(P(ca, *parts))
        return jax.tree_util.tree_unflatten(treedef, out)

    pspec = stacked_specs(state.params)
    return EpmcmcState(
        params=pspec,
        v=pspec,
        step=P(),
        key=P(ca, None),
        m_count=P(ca),
        m_mean=pspec,
        m_var=pspec,
    )


def batch_spec(mesh: Mesh, batch: PyTree) -> PyTree:
    """EP-MCMC batches are (C, b, ...) — chain axis sharded, rest local."""
    ca = chain_axes(mesh)
    return jax.tree.map(lambda l: P(ca, *([None] * (l.ndim - 1))), batch)


# ---------------------------------------------------------------------------
# the SGLD subposterior step (one transition of every chain, in parallel)
# ---------------------------------------------------------------------------


def _subposterior_neg_logpost(
    params: PyTree,
    cfg: ModelConfig,
    batch: Dict[str, jnp.ndarray],
    *,
    num_shards: int,
    shard_tokens: float,
) -> jnp.ndarray:
    """−log p_c(θ) up to a constant, for ONE chain (vmapped by the caller).

    CE is mean/token, so ``shard_tokens × CE`` is −log-lik of the whole shard
    (the N_c/B unbiased scaling); the Gaussian prior enters with weight 1/M
    (paper Eq. 2.1's underweighted prior).
    """
    total, _metrics = steps.loss_fn(params, cfg, batch)
    neg_loglik = shard_tokens * total
    sq = sum(
        jnp.sum(jnp.square(p.astype(jnp.float32))) for p in jax.tree.leaves(params)
    )
    neg_logprior = sq / (2.0 * PRIOR_SIGMA**2)
    return neg_loglik + neg_logprior / num_shards


def epmcmc_step(
    state: EpmcmcState,
    batch: Dict[str, jnp.ndarray],  # (C, b, ...) — one sub-batch per chain
    cfg: ModelConfig,
    *,
    num_shards: int,
    shard_tokens: float,
    step_size: float = 1e-6,
    rmsprop_decay: float = 0.99,
    rmsprop_eps: float = 1e-4,
    temperature: float = 1.0,
    burn_in: int = 0,
) -> Tuple[EpmcmcState, Dict[str, jnp.ndarray]]:
    """One pSGLD transition of all chains + streaming-moment update.

    ``temperature=0`` turns the transition into preconditioned SGD *per
    chain* — still embarrassingly parallel. The synchronous baseline lives in
    :func:`sgd_baseline_step`.
    """

    def one_chain(params, v, key, batch_c):
        nlp = functools.partial(
            _subposterior_neg_logpost,
            cfg=cfg,
            num_shards=num_shards,
            shard_tokens=shard_tokens,
        )
        loss, grads = jax.value_and_grad(lambda p: nlp(p, batch=batch_c))(params)
        # pSGLD: G = 1/(√v̂ + ε);  θ += −(ε/2)·G·∇nlp + √(ε·G·T)·ξ
        v_new = jax.tree.map(
            lambda vi, g: rmsprop_decay * vi
            + (1 - rmsprop_decay) * jnp.square(g.astype(jnp.float32)),
            v,
            grads,
        )
        key, knoise = jax.random.split(key)
        leaves, treedef = jax.tree.flatten(params)
        nkeys = jax.tree.unflatten(
            treedef, list(jax.random.split(knoise, len(leaves)))
        )

        def upd(p, g, vi, nk):
            G = 1.0 / (jnp.sqrt(vi) + rmsprop_eps)
            drift = -0.5 * step_size * G * g.astype(jnp.float32)
            noise = jnp.sqrt(step_size * G * temperature) * jax.random.normal(
                nk, p.shape, jnp.float32
            )
            return (p.astype(jnp.float32) + drift + noise).astype(p.dtype)

        params_new = jax.tree.map(upd, params, grads, v_new, nkeys)
        gnorm = jnp.sqrt(
            sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
        )
        return params_new, v_new, key, loss, gnorm

    p_new, v_new, k_new, losses, gnorms = jax.vmap(one_chain)(
        state.params, state.v, state.key, batch
    )
    # NB: metrics stay PER-CHAIN — even a scalar jnp.mean over the chain axis
    # would lower to a cross-chain all-reduce and break the zero-communication
    # property this mode exists to demonstrate. Average on the host if needed.

    # Streaming Welford moments, masked until burn-in completes. This is the
    # paper's §4 "online" combiner state: O(d) per chain, no samples stored.
    take = (state.step >= burn_in).astype(jnp.float32)
    n_new = state.m_count + take
    denom = jnp.maximum(n_new, 1.0)

    def welford(mean, var, p):
        p32 = p.astype(jnp.float32)
        bshape = (-1,) + (1,) * (p.ndim - 1)
        delta = p32 - mean
        mean_new = mean + (take.reshape(bshape) * delta) / denom.reshape(bshape)
        var_new = var + take.reshape(bshape) * delta * (p32 - mean_new)
        return mean_new, var_new

    flat_mean, treedef = jax.tree.flatten(state.m_mean)
    flat_var = jax.tree.leaves(state.m_var)
    flat_p = jax.tree.leaves(p_new)
    new_mean, new_var = [], []
    for mn, vr, p in zip(flat_mean, flat_var, flat_p):
        a, b = welford(mn, vr, p)
        new_mean.append(a)
        new_var.append(b)

    new_state = EpmcmcState(
        params=p_new,
        v=v_new,
        step=state.step + 1,
        key=k_new,
        m_count=n_new,
        m_mean=jax.tree.unflatten(treedef, new_mean),
        m_var=jax.tree.unflatten(treedef, new_var),
    )
    metrics = {"loss_per_chain": losses, "gnorm_per_chain": gnorms}
    return new_state, metrics


def sgd_baseline_step(
    state: EpmcmcState,
    batch: Dict[str, jnp.ndarray],
    cfg: ModelConfig,
    *,
    num_shards: int,
    shard_tokens: float,
    step_size: float = 1e-6,
    rmsprop_decay: float = 0.99,
    rmsprop_eps: float = 1e-4,
) -> Tuple[EpmcmcState, Dict[str, jnp.ndarray]]:
    """The synchronous strawman: same per-chain gradient, then *averaged
    across chains* (a data-axis psum — the collective EP-MCMC eliminates).
    Used by the dry-run to quantify the paper's deleted collective bytes."""

    def one_chain_grad(params, batch_c):
        nlp = functools.partial(
            _subposterior_neg_logpost,
            cfg=cfg,
            num_shards=num_shards,
            shard_tokens=shard_tokens,
        )
        return jax.value_and_grad(lambda p: nlp(p, batch=batch_c))(params)

    losses, grads = jax.vmap(one_chain_grad)(state.params, batch)
    # gradient averaging over the chain axis == DP all-reduce under GSPMD
    grads = jax.tree.map(lambda g: jnp.mean(g, axis=0, keepdims=True), grads)
    grads = jax.tree.map(
        lambda g, p: jnp.broadcast_to(g, p.shape), grads, state.params
    )

    def upd(p, g, v):
        v_new = rmsprop_decay * v + (1 - rmsprop_decay) * jnp.square(
            g.astype(jnp.float32)
        )
        G = 1.0 / (jnp.sqrt(v_new) + rmsprop_eps)
        return (p.astype(jnp.float32) - 0.5 * step_size * G * g.astype(jnp.float32)).astype(
            p.dtype
        ), v_new

    flat_p, treedef = jax.tree.flatten(state.params)
    outs = [
        upd(p, g, v)
        for p, g, v in zip(flat_p, jax.tree.leaves(grads), jax.tree.leaves(state.v))
    ]
    p_new = jax.tree.unflatten(treedef, [o[0] for o in outs])
    v_new = jax.tree.unflatten(treedef, [o[1] for o in outs])
    new_state = state._replace(params=p_new, v=v_new, step=state.step + 1)
    return new_state, {"loss_per_chain": losses}


# ---------------------------------------------------------------------------
# combination (the single communicating stage)
# ---------------------------------------------------------------------------


def combine_parametric_diag(state: EpmcmcState) -> GaussianMoments:
    """Full-θ parametric product (Eqs 3.1–3.2, diagonal/BvM form) from the
    streaming moments. Per-leaf; the reduce over the chain axis is the only
    cross-chain communication in the entire run (O(d) scalars)."""

    counts = jnp.maximum(state.m_count - 1.0, 1.0)

    def product(mean, var):
        C = mean.shape[0]
        cshape = (C,) + (1,) * (mean.ndim - 1)
        v = var / counts.reshape(cshape) + 1e-12
        flat_mean = mean.reshape(C, -1)
        flat_var = v.reshape(C, -1)
        mom = product_moments_diag(flat_mean, flat_var)
        return mom.mean.reshape(mean.shape[1:]), mom.cov.reshape(mean.shape[1:])

    means, variances = {}, {}
    flat, treedef = jax.tree_util.tree_flatten(state.m_mean)
    flat_v = jax.tree.leaves(state.m_var)
    out_m, out_v = [], []
    for mn, vr in zip(flat, flat_v):
        a, b = product(mn, vr)
        out_m.append(a)
        out_v.append(b)
    return GaussianMoments(
        mean=jax.tree.unflatten(treedef, out_m), cov=jax.tree.unflatten(treedef, out_v)
    )


def combine_gathered(
    key: jax.Array,
    samples: jnp.ndarray,  # (M, T, d_sub) all-gathered subset samples
    n_draws: int,
    *,
    combiner: str = "nonparametric",
    **options,
):
    """Final-stage exact combination of all-gathered subset samples.

    The combiner is resolved by registry name (``repro.core.combiners``), so
    the mesh run selects its combination strategy with the same string the
    CLI and benchmarks use — e.g. ``combiner="semiparametric"`` or
    ``combiner="nonparametric", n_batch=8, weight_eval="kernel"`` for the
    batched Pallas-scored IMG chains. Options the chosen combiner's
    signature does not declare are filtered out (the registry's
    option-forwarding convention), so one option dict can drive a sweep over
    rival combiners.

    Shape contract: ``samples`` must be the dense ``(M, T, d_sub)`` stack —
    a single :func:`gather_subset_samples` snapshot is ``(C, d_sub)`` and
    needs ``history=True`` there (T=1) or :func:`stack_subset_history`
    across steps first.
    """
    from repro.core.combiners import filter_options, get_combiner

    if samples.ndim != 3:
        raise ValueError(
            f"combine_gathered needs (M, T, d_sub) samples, got {samples.shape}; "
            "gather_subset_samples returns one (C, d_sub) snapshot — pass "
            "history=True there or stack snapshots with stack_subset_history"
        )
    fn = get_combiner(combiner)
    return fn(key, samples, n_draws, **filter_options(fn, options))


def gather_subset_samples(
    params: PyTree = None,
    paths: Sequence[str] | None = None,
    *,
    history: bool = False,
    chunk: Optional[Sequence[PyTree]] = None,
) -> jnp.ndarray:
    """Flatten a designated low-dim θ subset per chain → ``(C, d_sub)``.

    Default subset: final-norm scale (tiny, present in every arch). The
    exact (IMG) combiners require a ``(M, T, d_sub)`` history, not a single
    snapshot — ``history=True`` returns ``(C, 1, d_sub)`` (the documented
    ``samples[:, None, :]`` adapter), and per-step snapshots accumulate into
    the full layout with :func:`stack_subset_history`.

    ``chunk=`` is the streaming gather: pass a *window* of per-step stacked
    params (e.g. the last k post-burn-in states) and get the dense
    ``(C, k, d_sub)`` device slice back — exactly one
    ``StreamingCombiner.update`` chunk (see :func:`combine_stream`), so the
    driver folds windows as they land rather than stacking the history
    itself. Whether the *combiner* then holds the full ``(C, T, d_sub)``
    stack depends on its streaming state: ``online`` keeps O(d²) moments
    only; the buffered implementations re-accumulate the stack (their win
    is per-chunk trajectory + bitwise finals, not memory). Per-chain slices
    are concatenated host-side; no collective is ever emitted across the
    chain axes (the sampling step's HLO stays assertable collective-free,
    exactly as before)."""
    if chunk is not None:
        if params is not None:
            raise ValueError(
                "pass either one stacked params pytree or chunk= (a window "
                "of them), not both"
            )
        if len(chunk) == 0:
            raise ValueError("chunk= needs at least one per-step snapshot")
        return jnp.stack(
            [gather_subset_samples(p, paths) for p in chunk], axis=1
        )
    if params is None:
        raise ValueError("gather_subset_samples needs params (or chunk=)")
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    sel = []
    for path, leaf in flat:
        name = shd._path_str(path)
        if paths is None:
            if "final_norm" in name:
                sel.append(leaf)
        elif any(re.search(p, name) for p in paths):
            sel.append(leaf)
    if not sel:
        raise ValueError("subset selector matched no parameters")
    C = sel[0].shape[0]
    # jnp.array: force an owned buffer — with a single selected leaf the
    # reshape/astype/concatenate chain can alias ``params``, and snapshots
    # held across a donating step_fn (donate_argnums) would be deleted
    # under the caller's feet
    out = jnp.array(
        jnp.concatenate([s.reshape(C, -1).astype(jnp.float32) for s in sel], axis=1)
    )
    return out[:, None, :] if history else out


def combine_stream(
    key: jax.Array,
    chunks,
    n_draws: int,
    *,
    combiner: str = "nonparametric",
    **options,
):
    """Streaming counterpart of :func:`combine_gathered`.

    Folds an iterable of dense ``(M, C, d_sub)`` chunks — e.g. successive
    ``gather_subset_samples(chunk=window)`` slices — through the registry's
    :class:`~repro.core.combiners.api.StreamingCombiner` for ``combiner``
    and finalizes. For the buffered implementations the result is bitwise
    :func:`combine_gathered` on the concatenated stack; for ``online`` the
    full history is never materialized at all. Options follow the same
    per-signature filtering convention as the batch path.
    """
    from repro.core.combiners import filter_options, get_streaming_combiner

    sc = get_streaming_combiner(combiner)
    state = None
    for ch in chunks:
        if ch.ndim != 3:
            raise ValueError(
                f"combine_stream folds (M, C, d_sub) chunks, got {ch.shape}; "
                "use gather_subset_samples(chunk=window) to build them"
            )
        if state is None:
            state = sc.init(ch.shape[0], ch.shape[2])
        state = sc.update(state, ch)
    if state is None:
        raise ValueError("combine_stream needs at least one chunk")
    return sc.finalize(
        key, state, n_draws, **filter_options(sc.finalize, options)
    )


def stack_subset_history(snapshots: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Stack per-step ``(C, d_sub)`` subset gathers → ``(C, T, d_sub)``.

    The bridge from the streaming sampler to the combiner engine's dense
    layout: collect ``gather_subset_samples(state.params)`` every post-burn-in
    step (host-side list is fine — d_sub is tiny by construction), stack, and
    hand the result to :func:`combine_gathered`."""
    if len(snapshots) == 0:
        raise ValueError("stack_subset_history needs at least one snapshot")
    return jnp.stack([jnp.asarray(s) for s in snapshots], axis=1)
