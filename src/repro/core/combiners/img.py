"""The shared IMG engine behind every asymptotically exact combiner (§3.2/§3.3).

One Algorithm-1 core, parameterized by a *weight model* (:class:`ImgWeightModel`):

- nonparametric ``w_t`` (Eq. 3.5) with Gaussian KDE components       — §3.2
- semiparametric ``W_t`` (Hjort–Glad correction)                      — §3.3
- semiparametric components with ``w_t`` weights (higher acceptance)  — §3.3

replacing the two duplicated scan bodies the old ``combine.py`` monolith
carried. Complexity note (beyond-paper, algebraically exact): Algorithm 1 as
written recomputes ``w_t`` from scratch per proposal — O(dTM²) total. We
maintain the running component mean θ̄_t and Σ_m‖θ^m_{t_m}‖² incrementally,
using  Σ_m ‖θ_m − θ̄‖² = Σ_m ‖θ_m‖² − M·‖θ̄‖², so each single-index proposal
is O(d) and the whole run is O(dTM).

Execution modes (:func:`run_img`):

``n_batch=1`` (default)
    The classic serial chain: one sweep of M Metropolis-within-Gibbs index
    proposals per emitted draw.

``n_batch=B > 1``
    B independent IMG index-chains run under ``vmap``, each doing
    ``ceil(n_draws/B)`` sweeps from independently-initialized indices. Every
    chain is a bona-fide (shorter) run of Algorithm 1 — identical per-chain
    stationary distribution — so the serial O(n_draws·M) recursion becomes
    ~B-way parallel work. The bandwidth anneal uses a **shared global
    index**: chain b's sweep i anneals at h(i·B + b + 1), exactly the index
    the serial chain would use for that output row, so large B no longer
    stalls every chain at the under-annealed h(n_draws/B) endpoint.

``weight_eval="kernel"``
    The vectorized all-M-proposals-per-sweep variant: each sweep draws index
    proposals for *all* machines up front, evaluates all B·M candidate
    mixture weights in one batched call to the Pallas
    :func:`repro.kernels.img_weights.img_log_weights` kernel, and then runs
    the accept/reject recursion on O(M) scalars per site using an exact
    rank-one correction (below) — the sequential chain's distribution is
    preserved exactly, while all O(d)-heavy work becomes one kernel call plus
    one Gram matmul per sweep.

    Correction math: with base state (θ̄₀, Σ‖θ‖²₀), candidate deltas
    Δ_m = cand_m − θ_m and accepted set J at site m,

        log w(state_J ∪ {m}) = LW_m − (1/2h²)·[A − 2·s_B − (s_G + 2·g_m)/M]

    where LW_m is the kernel's base-state weight of the single-site-m
    modification, A = Σ_J (‖cand_j‖²−‖θ_j‖²), s_B = θ̄₀·S, s_G = ‖S‖²,
    g_m = S·Δ_m, S = Σ_J Δ_j — all maintained in O(M) per site from the
    precomputed Gram matrix G = ΔΔᵀ.

    Full semiparametric ``W_t`` rides the same recursion: the candidate
    state's mean is θ̄₀ + (S + Δ_m)/M and its per-sample term3 sum is
    extra₀ + Σ_J δaux_j + δaux_m with δaux_m = aux[m, c_m] − aux[m, t_m],
    so carrying S (B, d) and the accepted δaux sum (B,) exposes every
    quantity the state-level correction log N(θ̄ | μ̂_M, Σ̂_M + h²/M I) +
    Σ_m aux needs — O(B·d) per site, the same asymptotics as the Gram
    precompute. The pure-``w_t`` models skip all of it at trace time.

Programs: a registered combiner runs two jitted programs, the weight-model
build (:func:`model_arrays`, under the ``combine.img.model`` span) and the
chains (under ``combine.img.chain``). Their static arguments are the model's
kind and the options that fix shapes, so every later call with the same
shapes finds both programs in jit's in-memory cache: a weight model is
arrays (:class:`ImgModelArrays`) plus a kind, and its callables are rebuilt
inside the trace.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import bandwidth as bw
from repro.core.combiners.api import (
    CombineResult,
    counts_or_full,
    register,
    valid_masks,
)
from repro.core.gaussian import (
    GaussianMoments,
    fit_moments,
    log_normal_pdf,
    product_moments,
)
from repro.utils.spans import count, span

Schedule = Callable[[jnp.ndarray], jnp.ndarray]


class ImgWeightModel(NamedTuple):
    """What varies between §3.2 and §3.3: the weight terms and component law.

    ``aux`` (M, T): per-sample additive log-weight terms, gathered
    incrementally (semiparametric −log N(θ^m_t | μ̂_m, Σ̂_m); None ⇒ 0).
    ``extra_logweight(h)``: builds the state-level additive log-weight for
    bandwidth h (the semiparametric log N(θ̄ | μ̂_M, Σ̂_M + h²/M I) term;
    None ⇒ 0). ``draw(key, mean, h)``: one draw from the mixture component
    selected by the current indices. ``moments``: parametric product moments
    if the model computed them (reported in :class:`CombineResult`).
    """

    aux: Optional[jnp.ndarray]
    extra_logweight: Optional[Callable[[jnp.ndarray], Callable]]
    draw: Callable[[jax.Array, jnp.ndarray, jnp.ndarray], jnp.ndarray]
    moments: Optional[GaussianMoments]


# ---------------------------------------------------------------------------
# per-chain carry + incremental Gibbs sweep (Alg 1 lines 4–11)
# ---------------------------------------------------------------------------


# f32 matmuls: TPU's default runs them at bf16 input precision
_F32 = jax.lax.Precision.HIGHEST


class _ImgCarry(NamedTuple):
    key: jax.Array
    t_idx: jnp.ndarray  # (M,) current component indices
    theta_sel: jnp.ndarray  # (M, d) samples[m, t_idx[m]]
    mean: jnp.ndarray  # (d,) running θ̄_t
    sumsq: jnp.ndarray  # () running Σ_m ‖θ^m_{t_m}‖²
    extra: jnp.ndarray  # () running Σ_m aux[m, t_m] (semiparametric term3; 0 o.w.)
    n_accept: jnp.ndarray  # () accepted proposals


def _init_img_carry(
    key: jax.Array,
    samples: jnp.ndarray,
    counts: jnp.ndarray,
    aux: Optional[jnp.ndarray],
) -> _ImgCarry:
    M, T, d = samples.shape
    key, sub = jax.random.split(key)
    t0 = jax.random.randint(sub, (M,), 0, counts)  # Alg 1 line 1
    theta_sel = jnp.take_along_axis(samples, t0[:, None, None], axis=1)[:, 0, :]
    extra = jnp.zeros(()) if aux is None else jnp.sum(aux[jnp.arange(M), t0])
    return _ImgCarry(
        key=key,
        t_idx=t0,
        theta_sel=theta_sel,
        mean=jnp.mean(theta_sel, axis=0),
        sumsq=jnp.sum(theta_sel**2),
        extra=extra,
        n_accept=jnp.zeros(()),
    )


def _img_gibbs_sweep(
    carry: _ImgCarry,
    samples: jnp.ndarray,
    counts: jnp.ndarray,
    h: jnp.ndarray,
    aux: Optional[jnp.ndarray],
    extra_logweight: Optional[Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]],
) -> _ImgCarry:
    """One sweep of Alg 1 lines 4–11: propose a new index for each m in turn."""
    M, T, d = samples.shape
    inv_m = 1.0 / M

    def log_w(mean, sumsq, extra):
        sse = sumsq - M * jnp.sum(mean**2)
        lw = -0.5 * sse / (h**2)
        if extra_logweight is not None:
            lw = lw + extra_logweight(mean, extra)
        return lw

    def body(carry: _ImgCarry, m: jnp.ndarray) -> Tuple[_ImgCarry, None]:
        key, k_prop, k_acc = jax.random.split(carry.key, 3)
        c_m = jax.random.randint(k_prop, (), 0, counts[m])  # line 6
        theta_new = samples[m, c_m]
        theta_old = carry.theta_sel[m]
        mean_new = carry.mean + (theta_new - theta_old) * inv_m
        sumsq_new = carry.sumsq + jnp.sum(theta_new**2) - jnp.sum(theta_old**2)
        extra_new = (
            carry.extra
            if aux is None
            else carry.extra - aux[m, carry.t_idx[m]] + aux[m, c_m]
        )
        log_ratio = log_w(mean_new, sumsq_new, extra_new) - log_w(
            carry.mean, carry.sumsq, carry.extra
        )
        accept = jnp.log(jax.random.uniform(k_acc)) < log_ratio  # lines 7–8
        new_carry = _ImgCarry(
            key=key,
            t_idx=jnp.where(accept, carry.t_idx.at[m].set(c_m), carry.t_idx),
            theta_sel=jnp.where(accept, carry.theta_sel.at[m].set(theta_new), carry.theta_sel),
            mean=jnp.where(accept, mean_new, carry.mean),
            sumsq=jnp.where(accept, sumsq_new, carry.sumsq),
            extra=jnp.where(accept, extra_new, carry.extra),
            n_accept=carry.n_accept + accept,
        )
        return new_carry, None

    carry, _ = jax.lax.scan(body, carry, jnp.arange(M))
    return carry


def _run_chain(
    key: jax.Array,
    samples: jnp.ndarray,
    counts: jnp.ndarray,
    n_sweeps: int,
    schedule: Schedule,
    model: ImgWeightModel,
    anneal_offset: jnp.ndarray | int = 1,
    anneal_stride: int = 1,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One serial IMG chain: ``n_sweeps`` anneal steps, one draw per sweep.

    Sweep i anneals at global index ``anneal_offset + i·anneal_stride``.
    Batched runs pass offset b+1 / stride B so chain b's sweep i sits at the
    exact index the serial chain would use for output row i·B+b — the shared
    global anneal that keeps large-``n_batch`` runs as annealed as ``B=1``.
    """
    carry = _init_img_carry(key, samples, counts, model.aux)

    def step(carry: _ImgCarry, i: jnp.ndarray):
        h = schedule(anneal_offset + i * anneal_stride).astype(samples.dtype)  # line 3 (1-based)
        extra_lw = model.extra_logweight(h) if model.extra_logweight is not None else None
        carry = _img_gibbs_sweep(carry, samples, counts, h, model.aux, extra_lw)
        key, k_draw = jax.random.split(carry.key)
        carry = carry._replace(key=key)
        theta = model.draw(k_draw, carry.mean, h)  # line 12
        return carry, theta

    carry, draws = jax.lax.scan(step, carry, jnp.arange(n_sweeps))
    return draws, carry.n_accept


# ---------------------------------------------------------------------------
# vectorized all-M-proposals sweep (Pallas weight kernel on the hot path)
# ---------------------------------------------------------------------------


def _img_kernel_sweep(
    carry: _ImgCarry,  # batched: every leaf has a leading (B,) axis
    samples: jnp.ndarray,
    counts: jnp.ndarray,
    h: jnp.ndarray,
    aux: Optional[jnp.ndarray] = None,
    extra_lw: Optional[Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]] = None,
) -> _ImgCarry:
    """One sweep for B chains at once, weights evaluated by the Pallas kernel.

    All B·M candidate states (single-site modifications of each chain's base
    state) are scored in one ``img_log_weights`` call; the site recursion then
    runs on O(M) scalars per chain using the exact rank-one correction
    derived in the module docstring — bitwise different, distribution-exact.
    With ``extra_lw`` (semiparametric ``W_t``) the recursion also carries the
    accepted delta sum S (B, d) and the accepted δaux sum (B,), so every
    candidate's state-level correction term is evaluated from the base state
    in O(d) — the pure-``w_t`` path is untouched at trace time.
    """
    from repro.kernels.img_weights import img_log_weights

    M, T, d = samples.shape
    B = carry.mean.shape[0]
    dtype = samples.dtype

    keys = jax.vmap(lambda k: jax.random.split(k, 3))(carry.key)  # (B, 3, 2)
    key_next, k_prop, k_acc = keys[:, 0], keys[:, 1], keys[:, 2]
    c = jax.vmap(lambda k: jax.random.randint(k, (M,), 0, counts))(k_prop)  # (B, M)
    u = jax.vmap(lambda k: jax.random.uniform(k, (M,)))(k_acc)  # (B, M)

    cand = samples[jnp.arange(M)[None, :], c]  # (B, M, d) cand[b,m]=samples[m,c[b,m]]
    delta = cand - carry.theta_sel  # (B, M, d) Δ_m
    nsq = jnp.sum(cand**2, axis=-1) - jnp.sum(carry.theta_sel**2, axis=-1)  # (B, M)
    b_dot = jnp.einsum("bd,bmd->bm", carry.mean, delta, precision=_F32)  # θ̄₀·Δ_m
    gram = jnp.einsum("bmd,bnd->bmn", delta, delta, precision=_F32)  # Δ_j·Δ_m
    msq0 = jnp.sum(carry.mean**2, axis=-1)  # (B,)

    h32 = h.astype(jnp.float32)
    inv2h2 = 0.5 / (h32 * h32)
    log_norm = M * (d / 2.0) * jnp.log(2.0 * jnp.pi * h32 * h32)

    # All B·M single-site candidate states, scored in one kernel call. A
    # closed form for these base weights exists from the scalars above
    # (LW_m = lw_cur0 − inv2h2·(nsq_m − 2·b_m − G_mm/M)); routing through the
    # kernel instead is deliberate: it keeps the O(B·M²·d) bulk of the sweep
    # in the offloadable Pallas path (same asymptotics as the Gram matmul),
    # which is the TPU hot path this engine exists to feed.
    eye = jnp.eye(M, dtype=dtype)[None, :, :, None]  # (1, prop, machine, 1)
    theta_prop = (1.0 - eye) * carry.theta_sel[:, None, :, :] + eye * cand[:, :, None, :]
    lw_base = img_log_weights(theta_prop.reshape(B * M, M, d), h32).reshape(B, M)

    lw_cur0 = -(carry.sumsq - M * msq0) * inv2h2 - log_norm  # current-state weight

    semip = extra_lw is not None
    if semip:
        # δaux_m = aux[m, c_m] − aux[m, t_m]: per-site change of the Σ_m aux
        # term (zero when the model has no per-sample terms but still wants
        # the state-level correction — not a case the current models hit).
        if aux is not None:
            delta_aux = (
                aux[jnp.arange(M)[None, :], c]
                - aux[jnp.arange(M)[None, :], carry.t_idx]
            ).astype(jnp.float32)  # (B, M)
        else:
            delta_aux = jnp.zeros((B, M), jnp.float32)
        lw_cur0 = lw_cur0 + extra_lw(carry.mean, carry.extra)

    def site(state, m):
        if semip:
            lw_cur, acc_nsq, s_b, s_g, g, s_vec, acc_aux, a_mask, n_acc = state
        else:
            lw_cur, acc_nsq, s_b, s_g, g, a_mask, n_acc = state
        g_m = g[:, m]
        corr = -(acc_nsq - 2.0 * s_b - (s_g + 2.0 * g_m) / M) * inv2h2
        lw_prop = lw_base[:, m] + corr
        if semip:
            mean_m = carry.mean + (s_vec + delta[:, m]) / M  # candidate θ̄
            extra_m = carry.extra + acc_aux + delta_aux[:, m]
            lw_prop = lw_prop + extra_lw(mean_m, extra_m)
        accept = jnp.log(u[:, m]) < lw_prop - lw_cur  # (B,)
        af = accept.astype(jnp.float32)
        out = (
            jnp.where(accept, lw_prop, lw_cur),
            acc_nsq + af * nsq[:, m],
            s_b + af * b_dot[:, m],
            s_g + af * (2.0 * g_m + gram[:, m, m]),
            g + af[:, None] * gram[:, m, :],
        )
        if semip:
            out = out + (
                s_vec + af[:, None] * delta[:, m],
                acc_aux + af * delta_aux[:, m],
            )
        return out + (a_mask.at[:, m].set(accept), n_acc + af), None

    zeros_b = jnp.zeros((B,), jnp.float32)
    init = (
        lw_cur0.astype(jnp.float32),
        zeros_b,
        zeros_b,
        zeros_b,
        jnp.zeros((B, M), jnp.float32),
    )
    if semip:
        init = init + (jnp.zeros((B, d), dtype), zeros_b)
    init = init + (jnp.zeros((B, M), bool), zeros_b)
    final, _ = jax.lax.scan(site, init, jnp.arange(M))
    a_mask, n_acc = final[-2], final[-1]

    af = a_mask.astype(dtype)
    mean_new = carry.mean + jnp.einsum("bm,bmd->bd", af, delta, precision=_F32) / M
    sumsq_new = carry.sumsq + jnp.sum(af * nsq, axis=-1)
    return carry._replace(
        key=key_next,
        t_idx=jnp.where(a_mask, c, carry.t_idx),
        theta_sel=jnp.where(a_mask[:, :, None], cand, carry.theta_sel),
        mean=mean_new,
        sumsq=sumsq_new,
        extra=(carry.extra + final[6]) if semip else carry.extra,
        n_accept=carry.n_accept + n_acc,
    )


def _run_batched_kernel(
    key: jax.Array,
    samples: jnp.ndarray,
    counts: jnp.ndarray,
    n_sweeps: int,
    n_batch: int,
    schedule: Schedule,
    model: ImgWeightModel,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """B chains × ``n_sweeps`` vectorized sweeps → ((n_sweeps, B, d), (B,))."""
    M, T, d = samples.shape
    keys = jax.random.split(key, n_batch)
    carry = jax.vmap(lambda k: _init_img_carry(k, samples, counts, model.aux))(keys)

    def step(carry: _ImgCarry, i: jnp.ndarray):
        # Shared global anneal index: sweep i covers serial rows (i·B, (i+1)·B];
        # the kernel sweep scores all B chains at one scalar h, so use the
        # block's most-annealed index — after n_sweeps the bandwidth matches
        # the serial chain's h(n_draws) instead of stalling at h(n_draws/B).
        h = schedule((i + 1) * n_batch).astype(samples.dtype)
        extra_lw = (
            model.extra_logweight(h) if model.extra_logweight is not None else None
        )
        carry = _img_kernel_sweep(carry, samples, counts, h, model.aux, extra_lw)
        split = jax.vmap(jax.random.split)(carry.key)  # (B, 2, 2)
        carry = carry._replace(key=split[:, 0])
        theta = jax.vmap(lambda k, mn: model.draw(k, mn, h))(split[:, 1], carry.mean)
        return carry, theta

    carry, draws = jax.lax.scan(step, carry, jnp.arange(n_sweeps))
    return draws, carry.n_accept


# ---------------------------------------------------------------------------
# the engine entry point
# ---------------------------------------------------------------------------


# One program per shape, dtype and static option: later calls find it in
# jit's in-memory cache instead of re-tracing the chain.
@functools.partial(
    jax.jit, static_argnames=("n_draws", "n_batch", "weight_eval", "schedule", "weights")
)
def _chain_program(
    key: jax.Array,
    samples: jnp.ndarray,
    counts: Optional[jnp.ndarray],
    arrays: ImgModelArrays,
    *,
    n_draws: int,
    n_batch: int,
    weight_eval: str,
    schedule: Optional[Schedule],
    weights,
):
    """The IMG chains → (draws, acceptance rate, diagnostics).

    ``schedule=None`` anneals at Algorithm 1's rate times ``arrays.scale``;
    a caller's schedule is a static argument, so the same object finds the
    same program. ``weights``: see :func:`_weight_model`.
    """
    M, T, d = samples.shape
    n_sweeps = -(-n_draws // n_batch)  # ceil
    counts = counts_or_full(samples, counts)
    model = _weight_model(weights, arrays, samples)
    if schedule is None:
        schedule = bw.annealed(d, scale=arrays.scale)

    if weight_eval == "kernel":
        draws, n_acc = _run_batched_kernel(
            key, samples, counts, n_sweeps, n_batch, schedule, model
        )
        draws = draws.reshape(n_sweeps * n_batch, d)
        per_chain = n_acc / (n_sweeps * M)
        n_acc = jnp.sum(n_acc)
    elif n_batch == 1:
        draws, n_acc = _run_chain(key, samples, counts, n_sweeps, schedule, model)
        per_chain = (n_acc / (n_sweeps * M))[None]
    else:
        keys = jax.random.split(key, n_batch)
        offsets = jnp.arange(1, n_batch + 1, dtype=jnp.float32)
        draws, n_acc = jax.vmap(
            lambda k, off: _run_chain(
                k, samples, counts, n_sweeps, schedule, model,
                anneal_offset=off, anneal_stride=n_batch,
            )
        )(keys, offsets)
        draws = jnp.swapaxes(draws, 0, 1).reshape(n_sweeps * n_batch, d)
        per_chain = n_acc / (n_sweeps * M)
        n_acc = jnp.sum(n_acc)

    extras = {
        "n_batch": jnp.asarray(n_batch),
        "n_sweeps_per_chain": jnp.asarray(n_sweeps),
        "per_chain_acceptance": per_chain,
    }
    # ceil-rounding emits < n_batch surplus draws; drop the *earliest* (least
    # annealed) rows so the kept draws are the best of every chain.
    return draws[-n_draws:], n_acc / (n_sweeps * n_batch * M), extras


def _run(
    key: jax.Array,
    samples: jnp.ndarray,
    n_draws: int,
    arrays: ImgModelArrays,
    weights,
    *,
    counts: Optional[jnp.ndarray],
    schedule: Optional[Schedule],
    n_batch: int,
    weight_eval: str,
) -> CombineResult:
    """Run the chain program inside the ``combine.img.chain`` span."""
    M = samples.shape[0]
    n_draws = int(n_draws)
    n_batch = max(1, min(int(n_batch), n_draws))
    if weight_eval not in ("kernel", "incremental"):
        raise ValueError(f"unknown weight_eval {weight_eval!r}")
    with span("combine.img.chain"):
        count("img_sites", -(-n_draws // n_batch) * n_batch * M)
        draws, rate, extras = _chain_program(
            key, samples, counts, arrays,
            n_draws=n_draws, n_batch=n_batch, weight_eval=weight_eval,
            schedule=schedule, weights=weights,
        )
    return CombineResult(
        samples=draws, acceptance_rate=rate, moments=arrays.prod, extras=extras
    )


def run_img(
    key: jax.Array,
    samples: jnp.ndarray,
    n_draws: int,
    model: ImgWeightModel,
    *,
    counts: jnp.ndarray,
    schedule: Schedule,
    n_batch: int = 1,
    weight_eval: str = "incremental",
) -> CombineResult:
    """Run the IMG engine on a caller's weight model; package draws + diagnostics.

    ``n_batch``: number of independent index-chains (each does
    ``ceil(n_draws/n_batch)`` sweeps). ``weight_eval``: ``"incremental"``
    (O(d) single-site recursion) or ``"kernel"`` (vectorized sweeps scored by
    the Pallas ``img_weights`` kernel; supports every registered weight model
    including full semiparametric ``W_t``). The model's callables and
    ``schedule`` are static arguments of the chain program: the same objects
    find the same program, new ones trace it anew.
    """
    arrays = ImgModelArrays(
        aux=model.aux, prod=model.moments, lam_m=None, eta_m=None, scale=jnp.float32(1.0)
    )
    return _run(
        key, samples, n_draws, arrays, (model.extra_logweight, model.draw),
        counts=counts, schedule=schedule, n_batch=n_batch, weight_eval=weight_eval,
    )


# ---------------------------------------------------------------------------
# weight models: arrays built from one job's draws, callables rebuilt in trace
# ---------------------------------------------------------------------------

NONPARAMETRIC = "nonparametric"  # w_t weights, KDE components (§3.2)
SEMIPARAMETRIC = "semiparametric"  # W_t weights, semiparametric components
SEMIPARAMETRIC_W = "semiparametric_w"  # w_t weights, semiparametric components


class ImgModelArrays(NamedTuple):
    """The arrays of a weight model, built from one job's draws.

    ``aux`` as in :class:`ImgWeightModel`. ``prod``: the Gaussian product
    (μ̂_M, Σ̂_M) of the subposterior moments, ``lam_m`` = Σ̂_M^{-1},
    ``eta_m`` = Σ̂_M^{-1} μ̂_M (semiparametric kinds; None otherwise).
    ``scale``: the default anneal's scale (the pooled sample scale under
    ``rescale``, else 1).
    """

    aux: Optional[jnp.ndarray]
    prod: Optional[GaussianMoments]
    lam_m: Optional[jnp.ndarray]
    eta_m: Optional[jnp.ndarray]
    scale: jnp.ndarray


def model_arrays(
    samples: jnp.ndarray,
    counts: Optional[jnp.ndarray],
    *,
    kind: str,
    rescale: bool,
) -> ImgModelArrays:
    """Build the arrays of the ``kind`` weight model (§3.2 / §3.3)."""
    d = samples.shape[-1]
    scale = bw.pooled_scale(samples) if rescale else jnp.float32(1.0)
    if kind == NONPARAMETRIC:
        return ImgModelArrays(aux=None, prod=None, lam_m=None, eta_m=None, scale=scale)
    masks = valid_masks(samples, counts_or_full(samples, counts))

    # Parametric start: per-subposterior moments and their Gaussian product.
    moments = jax.vmap(lambda s, mk: fit_moments(s, mk))(samples, masks)
    prod = product_moments(moments.mean, moments.cov)
    lam_m = jnp.linalg.inv(prod.cov + 1e-10 * jnp.eye(d))  # Σ̂_M^{-1}
    eta_m = jnp.matmul(lam_m, prod.mean, precision=_F32)  # Σ̂_M^{-1} μ̂_M

    aux = None
    if kind == SEMIPARAMETRIC:
        # term3: −Σ_m log N(θ^m_{t_m} | μ̂_m, Σ̂_m), gathered incrementally.
        aux = -jax.vmap(lambda s, mom: log_normal_pdf(s, mom[0], mom[1]))(
            samples, (moments.mean, moments.cov)
        )  # (M, T)
    return ImgModelArrays(aux=aux, prod=prod, lam_m=lam_m, eta_m=eta_m, scale=scale)


def _nonparametric_draw(M, d, dtype, key, mean, h):
    """§3.2 component: N(θ̄_t, h²/M I)."""
    eps = jax.random.normal(key, (d,), dtype)
    return mean + eps * h / jnp.sqrt(jnp.asarray(M, dtype))


def _semiparametric_extra_logweight(arrays: ImgModelArrays, M, d, h):
    """W_t's state-level term for bandwidth h, as ``term(mean, extra_sum)``."""
    prod = arrays.prod
    cov_i = prod.cov + (h**2 / M) * jnp.eye(d)

    def term(mean, extra_sum):
        # + log N(θ̄ | μ̂_M, Σ̂_M + h²/M I) + Σ_m aux  (aux already −logN)
        return log_normal_pdf(mean, prod.mean, cov_i) + extra_sum

    return term


def _semiparametric_draw(arrays: ImgModelArrays, M, d, dtype, key, mean, h):
    """§3.3 component N(μ_t, Σ_t), in precision form: P = M/h² I + Λ_M,
    θ = μ_t + chol(P)^{-T} ε."""
    h2 = h**2
    prec = (M / h2) * jnp.eye(d) + arrays.lam_m
    chol_p = jnp.linalg.cholesky(prec)
    rhs = (M / h2) * mean + arrays.eta_m
    mu_t = jax.scipy.linalg.cho_solve((chol_p, True), rhs)
    eps = jax.random.normal(key, (d,), dtype)
    return mu_t + jax.scipy.linalg.solve_triangular(chol_p.T, eps, lower=False)


def _weight_model(weights, arrays: ImgModelArrays, samples: jnp.ndarray) -> ImgWeightModel:
    """The weight model's callables over ``arrays``.

    ``weights`` is a kind name, or the ``(extra_logweight, draw)`` callables
    of a caller's :class:`ImgWeightModel`.
    """
    if not isinstance(weights, str):
        extra_logweight, draw = weights
        return ImgWeightModel(
            aux=arrays.aux, extra_logweight=extra_logweight, draw=draw, moments=None
        )
    M, _, d = samples.shape
    if weights == NONPARAMETRIC:
        draw = functools.partial(_nonparametric_draw, M, d, samples.dtype)
        return ImgWeightModel(aux=None, extra_logweight=None, draw=draw, moments=None)
    extra_logweight = None
    if weights == SEMIPARAMETRIC:
        extra_logweight = functools.partial(_semiparametric_extra_logweight, arrays, M, d)
    draw = functools.partial(_semiparametric_draw, arrays, M, d, samples.dtype)
    return ImgWeightModel(
        aux=arrays.aux, extra_logweight=extra_logweight, draw=draw, moments=arrays.prod
    )


def nonparametric_model(samples: jnp.ndarray) -> ImgWeightModel:
    """§3.2: weights w_t (Eq. 3.5), components N(θ̄_t, h²/M I)."""
    arrays = model_arrays(samples, None, kind=NONPARAMETRIC, rescale=False)
    return _weight_model(NONPARAMETRIC, arrays, samples)


def semiparametric_model(
    samples: jnp.ndarray,
    counts: jnp.ndarray,
    *,
    nonparametric_weights: bool = False,
) -> ImgWeightModel:
    """§3.3: components N(μ_t, Σ_t) with Σ_t = (M/h² I + Σ̂_M^{-1})^{-1},
    μ_t = Σ_t (M/h² θ̄_t + Σ̂_M^{-1} μ̂_M).

    ``nonparametric_weights=False``: IMG weights W_t (paper's primary form)
        log W_t = log w_t + log N(θ̄_t | μ̂_M, Σ̂_M + h²/M I)
                  − Σ_m log N(θ^m_{t_m} | μ̂_m, Σ̂_m).
    ``nonparametric_weights=True``: the paper's second variant — weights w_t
        (higher IMG acceptance), same semiparametric components.
    """
    kind = SEMIPARAMETRIC_W if nonparametric_weights else SEMIPARAMETRIC
    arrays = model_arrays(samples, counts, kind=kind, rescale=False)
    return _weight_model(kind, arrays, samples)


# ---------------------------------------------------------------------------
# registered combiners
# ---------------------------------------------------------------------------


# the model build's program, like the chain's: one per shape and static option
_model_program = jax.jit(model_arrays, static_argnames=("kind", "rescale"))


def _combine(
    kind: str,
    key: jax.Array,
    samples: jnp.ndarray,
    n_draws: int,
    *,
    counts: Optional[jnp.ndarray],
    schedule: Optional[Schedule],
    rescale: bool,
    n_batch: int,
    weight_eval: str,
) -> CombineResult:
    """A registered IMG combiner: the model program, then the chain program."""
    with span("combine.img.model"):
        arrays = _model_program(
            samples, counts, kind=kind, rescale=bool(rescale) and schedule is None
        )
    return _run(
        key, samples, n_draws, arrays, kind,
        counts=counts, schedule=schedule, n_batch=n_batch, weight_eval=weight_eval,
    )


@register("nonparametric", "nonparametric_img")
def nonparametric(
    key: jax.Array,
    samples: jnp.ndarray,
    n_draws: int,
    *,
    counts: Optional[jnp.ndarray] = None,
    schedule: Optional[Schedule] = None,
    rescale: bool = False,
    n_batch: int = 1,
    weight_eval: str = "incremental",
    **_ignored,
) -> CombineResult:
    """Algorithm 1 — asymptotically exact sampling from ∏_m KDE(p_m)."""
    return _combine(
        NONPARAMETRIC, key, samples, n_draws, counts=counts, schedule=schedule,
        rescale=rescale, n_batch=n_batch, weight_eval=weight_eval,
    )


@register("semiparametric", "semiparametric_img")
def semiparametric(
    key: jax.Array,
    samples: jnp.ndarray,
    n_draws: int,
    *,
    counts: Optional[jnp.ndarray] = None,
    schedule: Optional[Schedule] = None,
    rescale: bool = False,
    nonparametric_weights: bool = False,
    n_batch: int = 1,
    weight_eval: str = "incremental",
    **_ignored,
) -> CombineResult:
    """§3.3 semiparametric combiner (see :func:`semiparametric_model`)."""
    return _combine(
        SEMIPARAMETRIC_W if nonparametric_weights else SEMIPARAMETRIC,
        key, samples, n_draws, counts=counts, schedule=schedule,
        rescale=rescale, n_batch=n_batch, weight_eval=weight_eval,
    )


@register("semiparametric_w", "semiparametric_wt")
def semiparametric_w(
    key: jax.Array,
    samples: jnp.ndarray,
    n_draws: int,
    *,
    counts: Optional[jnp.ndarray] = None,
    **options,
) -> CombineResult:
    """§3.3 second variant: semiparametric components, nonparametric weights."""
    options.pop("nonparametric_weights", None)
    return semiparametric(
        key, samples, n_draws, counts=counts, nonparametric_weights=True, **options
    )
