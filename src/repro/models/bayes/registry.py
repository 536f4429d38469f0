"""Bayes-model registry: the ``BayesModel`` protocol behind the EP pipeline.

A registered model packages everything the model-agnostic driver
(:mod:`repro.launch.mcmc_run`) needs to run the paper's full pipeline —
partition → sample → combine → score — without per-model branching:

- ``generate_data(key, n) -> (data, theta_true)``
- ``log_prior(theta) -> ()`` and ``log_lik(theta, data) -> ()`` (summed over
  the data's leading axis — the contract the subposterior builder and its
  ``count`` masking rely on)
- ``d``: dimension of the shared θ (what the combination stage sees)
- ``init_position(key, data_shard) -> θ0`` (defaults to a small-jitter
  origin start)
- ``shard_keys``: which dict keys hold per-datum arrays (``None`` = every
  leaf); global quantities (mixture weights …) are broadcast to every shard
  — this retires the driver's old ``only=("x",)`` gmm special-case
- ``default_sampler``: registry name the CLI falls back to
- optional Gibbs surface (paper §8.3 / criterion 3): ``gibbs_blocks(shard,
  M, *, step_size)`` building block updates against a concrete shard,
  ``gibbs_init(key, shard)`` for the extended position pytree, and
  ``gibbs_extract(positions)`` projecting stacked positions back to the
  shared ``(T, d)`` θ — latents stay shard-local, exactly as §8.3 requires.
  ``gibbs_counts=True`` declares that ``gibbs_blocks`` additionally accepts
  ``count=`` (the edge-pad valid-prefix convention) and masks the padded
  replicated rows out of its conditionals — such models run ``--sampler
  gibbs`` on non-divisible N; models without it keep requiring divisible N.
- optional fused likelihood pass: where ``fused_rows(n)`` holds for a shard
  of n rows, a gradient sampler's program makes ``shard_form(shard)`` once,
  before its chain scans, and hands it to ``log_lik`` for the whole-shard
  term; ``log_lik`` then gives its value and gradient from one pass over
  that form. ``log_lik`` still reads raw rows too (the padding correction's
  last row, SGLD's minibatches).

Models self-register at import time via :func:`register_model` (importing
:mod:`repro.models.bayes` populates the registry); consumers resolve them by
name with :func:`get_model` — mirroring ``repro.core.combiners`` and
``repro.samplers``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

PyTree = Any
Data = Any


@dataclasses.dataclass(frozen=True)
class BayesModel:
    """One paper-§8-style experiment family, pipeline-ready."""

    name: str
    generate_data: Callable[..., Tuple[Data, jnp.ndarray]]
    log_prior: Callable[[jnp.ndarray], jnp.ndarray]
    log_lik: Callable[[jnp.ndarray, Data], jnp.ndarray]
    d: int
    default_n: int = 50_000
    default_sampler: str = "rwmh"
    shard_keys: Optional[Tuple[str, ...]] = None
    init_position: Optional[Callable[[jax.Array, Data], jnp.ndarray]] = None
    gibbs_blocks: Optional[Callable[..., Any]] = None
    gibbs_init: Optional[Callable[[jax.Array, Data], PyTree]] = None
    gibbs_extract: Optional[Callable[[PyTree], jnp.ndarray]] = None
    gibbs_counts: bool = False  # gibbs_blocks masks padded rows via count=
    fused_rows: Optional[Callable[[int], bool]] = None
    shard_form: Optional[Callable[[Data], Data]] = None

    def initial_position(self, key: jax.Array, data_shard: Data) -> jnp.ndarray:
        """θ0 for one chain: model-provided init or jittered origin."""
        if self.init_position is not None:
            return self.init_position(key, data_shard)
        return 0.01 * jax.random.normal(key, (self.d,))

    @property
    def has_gibbs(self) -> bool:
        return self.gibbs_blocks is not None


_REGISTRY: Dict[str, BayesModel] = {}
_CANONICAL: Dict[str, BayesModel] = {}


def register_model(model: BayesModel, *aliases: str) -> BayesModel:
    """Add a model to the registry under its name (+ aliases)."""
    for key in (model.name, *aliases):
        if key in _REGISTRY:
            raise ValueError(f"model {key!r} already registered")
        _REGISTRY[key] = model
    _CANONICAL[model.name] = model
    return model


def get_model(name: str) -> BayesModel:
    """Resolve a model by registry name (raises KeyError with choices)."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown model {name!r}; available: {', '.join(available_models())}"
        ) from None


def available_models() -> Tuple[str, ...]:
    """All registered model names (aliases included), sorted."""
    return tuple(sorted(_REGISTRY))


def canonical_models() -> Tuple[str, ...]:
    """Primary registration names only (aliases dropped), sorted."""
    return tuple(sorted(_CANONICAL))
