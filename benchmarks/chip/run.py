#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

from the root of a checkout that holds the program (``src/``). The cell's
parts are found by name from ``BENCHMARK.json`` (see ``chipbench.cell``).
The run needs the chips the cell asks for: with no TPU, too few chips, or
Pallas kernels that would run interpreted, it exits 1 and prints no result.
JAX's compilation cache is kept at one fixed place in the checkout,
``.jax_cache/`` at its root.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))
# the compile cache lives in this checkout, at one fixed path; the program's
# enable_compile_cache() takes the directory from this variable
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(HERE.parents[1] / ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", type=Path, default=None,
                    help="keep the raw profiler trace in this directory")
    args = ap.parse_args(argv)

    from chipbench import cell, device, harness

    device.compile_cache()
    c = cell.find(args.workload)
    try:
        devices = device.chips_or_fail(c.chips)
    except device.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    result = harness.execute(
        c, args.seed, args.seconds, bool(args.trace), devices, T_START,
        keep_trace=args.keep_trace,
    )
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
