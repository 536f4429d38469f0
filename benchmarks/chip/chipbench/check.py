"""Draws against a reference, by their moments; and ``judge``.

The library of the comparison that a model file whose jobs yield draws
calls (``configs/logistic_regression.py``); :func:`judge` holds any
model file's numbers to a cell's limits.

A job yields the subposterior draws ``(M, T, d)`` (the sampler and model
layer) and the combined draws ``(T, d)`` (the combine layer). The reference
gives each subposterior's and the full posterior's mean and covariance
(float64). Each job's draws are reduced to their means and sds
(:func:`moments`), and compared by these numbers:

- ``sub_mean``: per shard, the root mean square over coordinates of
  (draw mean − reference mean) / reference sd; the worst shard;
- ``sub_sd``: per shard, the root mean square of log(draw sd / reference
  sd); the worst shard;
- ``comb_mean`` and ``comb_sd``: the same two for the combined draws
  against the full posterior;
- ``sub_mean_window`` and ``comb_mean_window``: ``sub_mean`` and
  ``comb_mean`` of the draw means averaged over the window's jobs.

The first four judge each job and read the worst job. One job's mean
carries the Monte Carlo error of 2,000 correlated draws; the window's jobs
sample the same posteriors with independent keys, so their averaged mean
sheds that error and exposes a bias a single job hides (a likelihood over
the wrong rows moves every job's mean the same way).

A root mean square over a shard's coordinates is steady from seed to seed
where a single coordinate's error is not, and a shard whose chain goes wrong
moves it as a whole. A number that is not finite reads as infinite, so it
fails every limit.
"""

from __future__ import annotations

import math
from typing import Dict, List, Mapping

import numpy as np

JOB_NAMES = ("sub_mean", "sub_sd", "comb_mean", "comb_sd")
WINDOW_NAMES = ("sub_mean_window", "comb_mean_window")
NAMES = JOB_NAMES + WINDOW_NAMES

Moments = Dict[str, np.ndarray]


def moments(sub_draws, combined) -> Moments:
    """Means and sds of one job's subposterior and combined draws."""
    sub = np.asarray(sub_draws, np.float64)
    comb = np.asarray(combined, np.float64)
    return {
        "sub_mean": sub.mean(axis=-2), "sub_sd": sub.std(axis=-2, ddof=1),
        "comb_mean": comb.mean(axis=-2), "comb_sd": comb.std(axis=-2, ddof=1),
    }


def _rms(values: np.ndarray) -> float:
    """Root mean square over the last axis, then the worst leading row."""
    values = np.where(np.isfinite(values), values, np.inf)
    if not values.size:
        return math.inf
    return float(np.max(np.sqrt(np.mean(np.square(values), axis=-1))))


def _sds(ref: Mapping[str, np.ndarray]):
    sub = np.sqrt(np.diagonal(ref["sub_cov"], axis1=-2, axis2=-1))
    return sub, np.sqrt(np.diag(ref["full_cov"]))


def numbers(m: Moments, ref: Mapping[str, np.ndarray]) -> Dict[str, float]:
    """The per-job numbers of one job's moments."""
    ref_sd, full_sd = _sds(ref)
    with np.errstate(divide="ignore", invalid="ignore"):
        return {
            "sub_mean": _rms((m["sub_mean"] - ref["sub_mean"]) / ref_sd),
            "sub_sd": _rms(np.log(m["sub_sd"] / ref_sd)),
            "comb_mean": _rms((m["comb_mean"] - ref["full_mean"]) / full_sd),
            "comb_sd": _rms(np.log(m["comb_sd"] / full_sd)),
        }


def window_numbers(ms: List[Moments], ref: Mapping[str, np.ndarray]) -> Dict[str, float]:
    """The window's numbers: the jobs' draw means, averaged."""
    if not ms:
        return {k: math.inf for k in WINDOW_NAMES}
    ref_sd, full_sd = _sds(ref)
    sub = np.mean([m["sub_mean"] for m in ms], axis=0)
    comb = np.mean([m["comb_mean"] for m in ms], axis=0)
    with np.errstate(invalid="ignore"):
        return {
            "sub_mean_window": _rms((sub - ref["sub_mean"]) / ref_sd),
            "comb_mean_window": _rms((comb - ref["full_mean"]) / full_sd),
        }


def readings(ms: List[Moments], ref: Mapping[str, np.ndarray]):
    """``(per-job readings, the worst of each number over the window)``."""
    per_job = [numbers(m, ref) for m in ms]
    worst = {k: max((r[k] for r in per_job), default=math.inf) for k in JOB_NAMES}
    worst.update(window_numbers(ms, ref))
    return per_job, worst


def judge(reading: Mapping[str, float], limits: Mapping[str, float]) -> bool:
    """True when every number in ``limits`` that ``reading`` holds is within
    its limit (a cell's limits file names the numbers it compares)."""
    return all(reading[k] <= float(v) for k, v in limits.items() if k in reading)
