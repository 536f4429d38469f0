"""The work counts behind ``mfu``, against hand counts."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import cell  # noqa: E402

MODEL = cell.load_module(HERE / "configs" / "logistic_regression.py")


def config(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


# (config, 4·N·d per transition, transitions per job)
HAND = [
    ("covtype-paper", 4 * 581_012 * 54, 200 + 333 + 2000),
    ("logreg-paper", 4 * 50_000 * 50, 200 + 333 + 2000),
]


@pytest.mark.parametrize("name,per_step,steps", HAND)
def test_job_flops_match_hand_counts(name, per_step, steps):
    cfg = config(name)
    assert MODEL.transition_flops(cfg) == per_step
    assert MODEL.job_flops(cfg) == per_step * steps


def test_covtype_job_is_318_gflop():
    assert MODEL.job_flops(config("covtype-paper")) == 317_887_933_536


def test_transition_count_is_the_chain_length_the_sampler_runs():
    # warmup, then burn-in, then T kept draws: the steps run_shard_chain takes
    cfg = {"N": 10, "d": 3, "warmup": 5, "burn_in": 2, "T": 7}
    assert MODEL.job_flops(cfg) == 4 * 10 * 3 * 14


def test_mfu_reader_divides_by_window_chips_and_peak():
    from chipbench import trace

    tr = trace.Trace(
        spans=[("job", 0.0, 2e9)],
        host=[],
        devices=[[("fusion", 0.0, 1e9)], [("fusion", 0.0, 1e9)]],
    )
    cfg = config("covtype-paper")
    c = cell.Cell("x", 2, cfg, {}, {}, MODEL, [], [])
    ctx = {"trace": tr, "cell": c, "jobs": 3, "peak": {"bf16_flops": 197e12}}
    want = 100.0 * 3 * 317_887_933_536 / (2.0 * 2 * 197e12)
    assert cell.reader("mfu").read(ctx) == pytest.approx(want, rel=1e-12)
    assert cell.reader("mfu").read({**ctx, "peak": None}) is None
