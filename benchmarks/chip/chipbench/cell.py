"""One cell: a configuration under a traffic mix, found by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix. Every
part of a cell is a file of its own, found by that name:

- ``configs/<file>.json``: the configuration as it is run (model, N, d, M,
  sampler, warmup, T, burn-in, prior) and ``model_file``, the module beside
  it with the data generator, the work count, the plain reference and the
  check's numbers (its contract is in ``chipbench.harness``);
- ``traffic/<traffic>.json``: what one job is (the combiner and its options,
  the stream cadence) and how jobs are issued;
- ``limits/<workload>.json``: the limit of each number the check compares,
  among those the model file's ``NUMBERS`` name;
- ``metrics/<metric>.py``: one reader per per-layer metric.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, NamedTuple

BENCH_DIR = Path(__file__).resolve().parents[1]
REPO = BENCH_DIR.parents[1]


def load_module(path: Path) -> ModuleType:
    """Import a file by path under a name of its own."""
    path = Path(path)
    name = "chipbench_" + "_".join(path.with_suffix("").parts[-2:]).replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_json(path: Path) -> Any:
    return json.loads(Path(path).read_text())


class Cell(NamedTuple):
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    model: ModuleType  # the configuration's model file
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def applies(metric: Dict[str, Any], workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def find(workload: str, bench: Dict[str, Any] | None = None) -> Cell:
    """Resolve ``workload`` to its files; unknown names raise KeyError."""
    bench = bench if bench is not None else read_json(REPO / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    entry = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = read_json(REPO / configs[entry["config"]]["file"])
    model = load_module(BENCH_DIR / "configs" / config["model_file"])
    limits = read_json(BENCH_DIR / "limits" / f"{workload}.json")
    unknown = set(limits) - set(model.NUMBERS)
    if unknown:
        raise ValueError(f"limits of {workload!r} name no number of its model file: "
                         f"{sorted(unknown)}")
    return Cell(
        name=workload,
        chips=int(entry["chips"]),
        config=config,
        traffic=read_json(BENCH_DIR / "traffic" / f"{entry['traffic']}.json"),
        limits=limits,
        model=model,
        end_to_end=[m for m in bench["end_to_end"] if applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if applies(m, workload)],
    )


def reader(metric: str) -> ModuleType:
    """The per-layer metric's reader, ``metrics/<metric>.py``."""
    return load_module(BENCH_DIR / "metrics" / f"{metric}.py")
