"""Benchmark suite entry point: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # quick (CPU-sized)
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale chains
  PYTHONPATH=src python -m benchmarks.run --only fig4_gmm
  PYTHONPATH=src python -m benchmarks.run --json perf/   # + BENCH_<ts>.json

Emits CSV rows (bench,case,metric,value,units,extra) to stdout; ``--json``
additionally writes the same rows as machine-readable JSON — the
perf-trajectory files this repo accumulates across PRs.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
import traceback

from benchmarks.common import HEADER
from repro.utils.compile_cache import enable_compile_cache

BENCHES = [
    ("fig1+2_logreg", "benchmarks.bench_logreg"),
    ("fig3_covtype", "benchmarks.bench_covtype"),
    ("fig3_dims", "benchmarks.bench_dims"),
    ("fig4_gmm", "benchmarks.bench_gmm"),
    ("fig5_poisson", "benchmarks.bench_poisson"),
    ("samplers", "benchmarks.bench_samplers"),
    ("matrix", "benchmarks.bench_matrix"),
    ("combine", "benchmarks.bench_combine"),
    # "stream", not "stream_combine": --only combine must keep selecting the
    # combine bench alone (substring filter)
    ("stream", "benchmarks.bench_stream"),
    ("serve", "benchmarks.bench_serve"),
    ("kernels", "benchmarks.bench_kernels"),
    ("roofline", "benchmarks.bench_roofline"),
]


def _json_path(arg: str, timestamp: str) -> str:
    """Anything not explicitly a ``.json`` file is a directory (created on
    demand) that gets an auto BENCH_<ts>.json name."""
    if arg.endswith(".json") and not os.path.isdir(arg):
        return arg
    return os.path.join(arg, f"BENCH_{timestamp}.json")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true", help="paper-scale chain lengths")
    ap.add_argument("--only", default=None, help="substring filter on bench name")
    ap.add_argument(
        "--json", default=None, metavar="PATH",
        help="also write rows as JSON (a directory gets BENCH_<timestamp>.json)",
    )
    args = ap.parse_args(argv)
    enable_compile_cache()

    timestamp = time.strftime("%Y%m%d_%H%M%S")
    print(HEADER)
    failures = 0
    all_rows = []
    for name, module in BENCHES:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            mod = importlib.import_module(module)
            rows = mod.run(full=args.full)
            for row in rows:
                print(row.csv())
            all_rows += [
                dict(bench=r.bench, case=r.case, metric=r.metric,
                     value=r.value, units=r.units, extra=r.extra)
                for r in rows
            ]
            print(f"# {name}: {len(rows)} rows in {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception:
            failures += 1
            print(f"# {name}: FAILED\n{traceback.format_exc()}", file=sys.stderr)

    if args.json is not None:
        path = _json_path(args.json, timestamp)
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(
                {"timestamp": timestamp, "full": args.full, "failures": failures,
                 "rows": all_rows},
                f, indent=1,
            )
        print(f"# wrote {len(all_rows)} rows to {path}", file=sys.stderr)
    return failures


if __name__ == "__main__":
    sys.exit(main())
