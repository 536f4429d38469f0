"""One run of one cell: set-up, the measured window, the check, the result.

Set-up makes the data on the device from the seed, partitions it and runs
one whole job, which compiles every program the window uses. That job is
then summarized and dropped, outside both clocks, so the summary's own
programs compile before the window; its summary is not judged. The window
then runs whole jobs back to back for ``--seconds`` of job time: each job
is summarized as it ends, with the window's clock stopped, and only its
summary is kept. With ``--trace 1`` the traffic mix's ``trace_jobs`` jobs
follow under the profiler, and are summarized once the profiler has
stopped; the per-layer metrics are read from their trace. Then the device's
peak memory is read, and every job is compared with the reference: the
check is the same with and without the trace.

The check belongs to the configuration's model file, which provides:

- ``make_data(key, cfg)``: the data, on the device, from the seed;
- ``job_flops(cfg)``: the algorithm's work in one job;
- ``handoff(sample)``: what the combine stage takes from the sample
  stage's result (see ``chipbench.jobs``);
- ``reference(data, cfg)``: the plain reference, once a run, after the
  window, from the arrays ``make_data`` made;
- ``summarize(output, cfg)``: one job's ``jobs.Output`` reduced to what
  the check keeps, called as the job ends. It returns host values (numpy
  arrays, Python numbers) or arrays that hold nothing of the job, so that
  the job's device buffers are freed before the next job starts;
- ``readings(summaries, ref, cfg)``: ``(per_job, window)``, a dict of the
  job-scope numbers for each job, and a dict of every number over the
  window (a job-scope number's worst job);
- ``NUMBERS``: ``{name: {"layer": "sampling" | "combine", "scope": "job" |
  "window"}}``, the numbers a cell's limits may name;
- ``bayes_model(cfg)``, optional: the program's ``BayesModel`` that the jobs
  sample, built with the program's public constructor from what the
  configuration states. Without it the jobs sample the program's registered
  ``cfg["model"]``. The program keeps its compiled sampling programs per
  process under the model's ``name``, so a model built here takes a name of
  its own for every configuration that changes it.

A job fails where one of its job-scope numbers is over its limit; every
job fails where a window-scope number is. Summaries and the reference are
dicts of arrays.
"""

from __future__ import annotations

import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import jax

from chipbench import check, trace
from chipbench.cell import Cell, reader
from chipbench.device import CompileClock, describe, peak_of
from chipbench.jobs import Jobs, seed_key


def _log(**fields) -> None:
    print(json.dumps(fields), file=sys.stderr, flush=True)


def _traced(jobs: Jobs, first: int, n: int, chips: int, keep: Optional[Path]):
    """Run ``n`` jobs under the profiler; ``(outputs, Trace)``, with the
    outputs not yet summarized, so that no summary enters the trace."""
    log_dir = Path(keep) if keep else Path(tempfile.mkdtemp(prefix="chipbench_trace_"))
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # no per-function Python events
        options.enable_hlo_proto = False
        jax.profiler.start_trace(str(log_dir), profiler_options=options)
        try:
            outputs = [jobs.run(first + i) for i in range(n)]
        finally:
            jax.profiler.stop_trace()
        return outputs, trace.load(trace.find_xplane(log_dir), chips)
    finally:
        if not keep:
            shutil.rmtree(log_dir, ignore_errors=True)


def failures(numbers: Dict[str, Dict[str, str]], limits: Dict[str, float],
             per_job: List[Dict[str, float]], window: Dict[str, float]) -> int:
    """The jobs that fail the cell's limits, judged by each number's scope."""
    def scoped(scope):
        return {k: v for k, v in limits.items() if numbers[k]["scope"] == scope}

    if not check.judge(window, scoped("window")):
        return len(per_job)  # the window's number is every job's
    return sum(not check.judge(r, scoped("job")) for r in per_job)


def execute(cell: Cell, seed: int, seconds: float, traced: bool,
            devices: List[jax.Device], t_start: float,
            keep_trace: Optional[Path] = None, min_jobs: int = 1) -> Dict[str, Any]:
    """Run the cell once and return the result line's object; the window
    holds at least ``min_jobs`` jobs."""
    clock = CompileClock()
    cfg = cell.config
    key = seed_key(seed)
    data = cell.model.make_data(jax.random.fold_in(key, 0), cfg)
    jobs = Jobs(cell, data, key)
    warm = jobs.run(0)  # compiles every program the window drives
    setup_s = time.perf_counter() - t_start
    jobs.summarize(warm)  # compiles the summary's programs; not judged
    del warm
    setup_compiles = clock.snapshot()

    breakdown = None
    done, window_s = jobs.loop(1, seconds, min_jobs)
    timing = {"window_s": window_s, "job_s": window_s / len(done)}
    window_compiles = CompileClock.since(setup_compiles, clock.snapshot())
    traced_jobs = int(cell.traffic["trace_jobs"])
    if traced:
        # a few more jobs under the profiler, checked with the window's
        more, tr = _traced(jobs, 1 + len(done), traced_jobs, cell.chips, keep_trace)
        done += [jobs.summarize(o) for o in more]
        del more
    dev = describe(devices)

    metrics: Dict[str, Dict[str, Any]] = {}
    if traced:
        ctx = {"trace": tr, "cell": cell, "jobs": traced_jobs,
               "peak": peak_of(dev["kind"]) if dev["platform"] == "tpu" else None}
        for m in cell.per_layer:
            value = reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        span = trace.busy_seconds(tr)
        if span is not None:
            dev["busy_s"], dev["window_s"] = span
        breakdown = {"device_ops": trace.top_ops(tr), "idle_gaps": trace.idle_gaps(tr)}
    else:
        values = {"job_s": timing["job_s"], "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    # the check: every job of the window against the reference
    t_ref = time.perf_counter()
    model = cell.model
    ref = model.reference(data, cfg)
    per_job, window = model.readings([d.summary for d in done], ref, cfg)
    failed = failures(model.NUMBERS, cell.limits, per_job, window)
    job_numbers = [k for k, n in model.NUMBERS.items() if n["scope"] == "job"]
    _log(info="run", workload=cell.name, seed=seed, trace=int(traced), setup_s=setup_s,
         setup_compiles=setup_compiles, window_compiles=window_compiles,
         jobs=len(done), sample_s=[d.sample_s for d in done],
         combine_s=[d.combine_s for d in done],
         summarize_s=[d.summarize_s for d in done], reference_s=time.perf_counter() - t_ref,
         readings={k: [r[k] for r in per_job] for k in job_numbers}, **timing)
    result: Dict[str, Any] = {
        "correct": bool(done) and failed == 0,
        "attempted": len(done),
        "failed": failed,
        "metrics": metrics,
        "device": dev,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = {
        k: {"value": window[k], "limit": float(limit)} for k, limit in cell.limits.items()
    }
    return result


def finite(obj):
    """JSON has no infinity: an infinite reading prints as a large number."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return 1e300 if obj > 0 else -1e300
    if isinstance(obj, dict):
        return {k: finite(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [finite(v) for v in obj]
    return obj


def report(result: Dict[str, Any]) -> None:
    """The check's numbers as the last lines of standard error, then the
    result as the last line of standard output."""
    for name, c in result["check"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr, flush=True)
    print(json.dumps(finite(result)), flush=True)
