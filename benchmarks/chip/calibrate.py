#!/usr/bin/env python3
"""Readings of the check's numbers, for setting a cell's limits.

    python3 benchmarks/chip/calibrate.py --workload <name> \\
        --seeds 11,12,13 --jobs 20 --control-seeds 21,22,23 --control-jobs 10

One process on the chip: for each of ``--seeds`` the program's ``--jobs``
jobs on that seed's data, as a run makes them, and the readings a run would
print (the lower reading is the largest of these); for each of
``--control-seeds`` the control, the plain reference sampler put in the
program's place in bfloat16, over ``--control-jobs`` jobs, and the same
sampler in float32 beside it as a witness. One JSON line per reading on
standard output; ``--save`` keeps every job's summary. The benchmark's own
runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(HERE.parents[1] / ".jax_cache")


def _save(save, name: str, ms, ref) -> None:
    """Every job's summary and the reference, to recompute any number."""
    import numpy as np

    if save is None:
        return
    save.mkdir(parents=True, exist_ok=True)
    arrays = {f"job{j}_{k}": v for j, m in enumerate(ms) for k, v in m.items()}
    arrays.update({f"ref_{k}": v for k, v in ref.items()})
    np.savez_compressed(save / f"{name}.npz", **arrays)


def control_reading(c, seed: int, dtype_name: str, jobs: int, save=None) -> dict:
    """The reference sampler in the program's place, checked as a run is:
    ``jobs`` jobs with keys of their own on one seed's data."""
    import jax
    import jax.numpy as jnp

    from chipbench import check
    from chipbench.jobs import seed_key

    m, cfg = c.model, c.config
    key = seed_key(seed)
    data = m.make_data(jax.random.fold_in(key, 0), cfg)
    t0 = time.perf_counter()
    summaries = [
        m.summarize(m.control(jax.random.fold_in(jax.random.fold_in(key, 1), j), data,
                              cfg, getattr(jnp, dtype_name)), cfg)
        for j in range(1, jobs + 1)
    ]
    seconds = (time.perf_counter() - t0) / jobs
    ref = m.reference(data, cfg)
    _save(save, f"{c.name}_control_{dtype_name}_{seed}", summaries, ref)
    per_job, worst = m.readings(summaries, ref, cfg)
    return {"workload": c.name, "who": f"control_{dtype_name}", "seed": seed,
            "jobs": jobs, "seconds_per_job": seconds, "reading": worst,
            "correct": check.judge(worst, c.limits)}


def program_reading(c, seed: int, jobs_per_seed: int, save=None) -> dict:
    """The program's jobs on one seed's data, checked as a run checks them."""
    import jax

    from chipbench import check
    from chipbench.jobs import Jobs, seed_key

    m, cfg = c.model, c.config
    key = seed_key(seed)
    data = m.make_data(jax.random.fold_in(key, 0), cfg)
    jobs = Jobs(c, data, key)
    summaries = [m.summarize(jobs.run(j), cfg) for j in range(1, jobs_per_seed + 1)]
    ref = m.reference(data, cfg)
    _save(save, f"{c.name}_program_{seed}", summaries, ref)
    per_job, worst = m.readings(summaries, ref, cfg)
    return {"workload": c.name, "who": "program", "seed": seed, "jobs": len(summaries),
            "reading": worst, "correct": check.judge(worst, c.limits)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--jobs", type=int, default=1)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control-jobs", type=int, default=1)
    ap.add_argument("--save", type=Path, default=None,
                    help="write every job's summary and the reference here")
    args = ap.parse_args(argv)

    from chipbench import cell, device

    device.compile_cache()
    c = cell.find(args.workload)
    try:
        device.chips_or_fail(c.chips)
    except device.NoChip as e:
        print(f"calibrate.py: {e}", file=sys.stderr)
        return 1
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    for seed in seeds:
        print(json.dumps(program_reading(c, seed, args.jobs, args.save)), flush=True)
    for seed in control_seeds:
        for dtype_name in ("bfloat16", "float32"):
            print(json.dumps(control_reading(
                c, seed, dtype_name, args.control_jobs, args.save)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
