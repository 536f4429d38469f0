"""Posterior query handlers: request dict in, response dict out.

Every handler is a pure function of a :class:`~repro.serve.state.ServeState`
plus the request parameters — no asyncio, no transport — so the whole query
surface is unit-testable synchronously and the server's TCP loop is a thin
line-framing shell around :func:`answer`.

Query surface (``op`` field):

``mean_cov``
    Posterior mean and covariance of the current estimate cloud (plus the
    per-dimension marginal std).
``quantiles``
    Marginal quantiles per dimension at ``probs`` (default five-number-ish
    ``0.05/0.25/0.5/0.75/0.95``).
``draws``
    ``n`` predictive draws from the estimate cloud — a deterministic seeded
    subsample, so the same request against the same snapshot returns the
    same draws.
``logpdf``
    Unnormalized log posterior density at ``points`` via the batched
    machine-KDE scorer (PR 8): Σ_m log p̂_m on the accumulated draw buffer
    (``reduce="product"`` — the paper's subposterior-product density; also
    accepts ``"mixture"``).
``status``
    Staleness metadata only (no estimate required).

Responses are ``{"ok": True, "op", "combiner", "result", "staleness"}`` or
``{"ok": False, "error": {"code", "reason", ...}, "staleness"}``. The typed
:class:`~repro.core.combiners.api.EstimateUnavailable` maps to ``code=503``
(the combiner folds but cannot refresh — retry another name or wait for
completion); a request the validators refuse (:class:`BadRequest`: unknown
op or combiner, bad params) maps to ``code=400``. Any other failure is the
server's own (a kernel that does not compile, a device error) and
propagates: :func:`answer` raises it, and the TCP loop reports it as
``code=500``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from repro.core.combiners import EstimateUnavailable, counts_or_full
from repro.core.combiners.density import machine_kde_scores, masked_silverman
from repro.serve.state import ServeState
from repro.utils.spans import span

DEFAULT_PROBS = (0.05, 0.25, 0.5, 0.75, 0.95)


class BadRequest(ValueError):
    """A request the query surface refuses as malformed (``code=400``)."""


def _param(params: Dict[str, Any], key: str, cast, default):
    """``cast(params.get(key, default))``, a :class:`BadRequest` if it fails."""
    try:
        return cast(params.get(key, default))
    except (TypeError, ValueError) as exc:
        raise BadRequest(f"{key}: {exc}") from None


def handle_mean_cov(state: ServeState, name: str, params: Dict[str, Any]):
    snap = state.snapshot(name)
    return {
        "mean": snap.mean.tolist(),
        "cov": snap.cov.tolist(),
        "std": np.sqrt(np.clip(np.diag(snap.cov), 0.0, None)).tolist(),
        "n_estimate": int(snap.samples.shape[0]),
    }


def handle_quantiles(state: ServeState, name: str, params: Dict[str, Any]):
    probs = _param(params, "probs", lambda ps: [float(p) for p in ps], DEFAULT_PROBS)
    if not probs or any(not (0.0 <= p <= 1.0) for p in probs):
        raise BadRequest(f"probs must lie in [0, 1], got {probs}")
    snap = state.snapshot(name)
    q = np.quantile(snap.samples, probs, axis=0)  # (P, d)
    return {"probs": probs, "quantiles": q.tolist()}


def handle_draws(state: ServeState, name: str, params: Dict[str, Any]):
    n = _param(params, "n", int, 16)
    if n <= 0:
        raise BadRequest(f"n must be positive, got {n}")
    seed = _param(params, "seed", int, 0)
    snap = state.snapshot(name)
    # deterministic per (snapshot, seed): same request, same draws
    idx = np.random.default_rng(seed).integers(0, snap.samples.shape[0], size=n)
    return {"draws": snap.samples[idx].tolist(), "seed": seed}


def handle_logpdf(state: ServeState, name: str, params: Dict[str, Any]):
    import jax.numpy as jnp

    if "points" not in params:
        raise BadRequest("logpdf needs 'points': one d-vector or a list of them")
    pts = _param(params, "points", lambda p: np.asarray(p, dtype=np.float32), None)
    if pts.ndim == 1:
        pts = pts[None, :]
    if pts.ndim != 2:
        raise BadRequest(f"points must be (d,) or (Q, d), got shape {pts.shape}")
    reduce = str(params.get("reduce", "product"))
    if reduce not in ("product", "mixture"):
        raise BadRequest(f"reduce must be 'product' or 'mixture', got {reduce!r}")
    theta, counts = state.logpdf_inputs()
    if pts.shape[1] != theta.shape[-1]:
        raise BadRequest(
            f"points are {pts.shape[1]}-dimensional, posterior is "
            f"{theta.shape[-1]}-dimensional"
        )
    h = masked_silverman(theta, counts_or_full(theta, counts))
    scores = machine_kde_scores(
        jnp.asarray(pts), theta, counts, h, reduce=reduce
    )
    return {
        "log_density": np.asarray(scores).tolist(),
        "reduce": reduce,
        "normalized": False,  # Σ_m log p̂_m is the unnormalized product score
    }


def handle_status(state: ServeState, name: str, params: Dict[str, Any]):
    return {
        "combiners": list(state.setup.names),
        "ops": sorted(HANDLERS),
        "n_estimate": state.n_estimate,
    }


HANDLERS = {
    "mean_cov": handle_mean_cov,
    "quantiles": handle_quantiles,
    "draws": handle_draws,
    "predictive": handle_draws,  # alias
    "logpdf": handle_logpdf,
    "status": handle_status,
}


@span("serve.answer")
def answer(state: ServeState, request: Dict[str, Any]) -> Dict[str, Any]:
    """Dispatch one request dict. Refused requests and unavailable estimates
    become typed ``{"ok": False, "error": ...}`` responses (still carrying
    staleness, so even a 503 tells the reader where the stream is); any other
    failure is raised to the caller, never reported as the client's fault."""
    op = request.get("op")
    name: Optional[str] = request.get("combiner") or (
        state.setup.names[0] if state.setup.names else None
    )
    base: Dict[str, Any] = {"op": op, "combiner": name}
    if "id" in request:
        base["id"] = request["id"]
    try:
        handler = HANDLERS.get(op)
        if handler is None:
            raise BadRequest(
                f"unknown op {op!r}; available: {sorted(HANDLERS)}"
            )
        if name not in state.setup.names:
            raise BadRequest(
                f"combiner {name!r} not served; serving: {state.setup.names}"
            )
        result = handler(state, name, request)
        return {
            "ok": True, **base,
            "result": result,
            "staleness": state.staleness(name),
        }
    except EstimateUnavailable as exc:
        return {
            "ok": False, **base,
            "error": {"code": 503, "reason": exc.reason, "combiner": exc.combiner},
            "staleness": state.staleness(name),
        }
    except BadRequest as exc:
        return {
            "ok": False, **base,
            "error": {"code": 400, "reason": str(exc)},
            "staleness": state.staleness(
                name if name in state.setup.names else None
            ),
        }
