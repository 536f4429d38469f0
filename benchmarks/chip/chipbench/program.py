"""The program's own spans and counters, for the metrics that read them.

The program records spans (``repro.utils.spans``): each opens a profiler
annotation, so it sits in the trace's host events on the thread of the
benchmark's spans, and each closed span leaves a record with its counters
in an in-memory ring. The stage spans are ``sample.stage`` (its ``steps``
counter: each chain's sequential transitions) and ``combine.stage``; a
span's counters cover the spans nested in it, among them the executables
JAX obtained (``executables``, ``backend_compile_s``) and
``combine.img.chain``'s ``img_sites``.

The traced jobs are the last to run before the readers, so the last
``jobs`` records of a stage are theirs, in the order of the traced spans.
A program that records no spans (one older than ``repro.utils.spans``)
gives ``None`` here, and the metrics that read it are left out.
"""

from __future__ import annotations

from typing import List, Optional

from chipbench import trace

STAGES = ("sample.stage", "combine.stage")

# JAX's own host events while it traces and lowers a program
# (``jax/_src/interpreters/partial_eval.py``, ``pxla.py``). Obtaining the
# executable afterwards, compiled or read from the persistent cache, is
# timed by the records' ``backend_compile_s``: no host event marks a cache
# read, and ``backend_compile_and_load`` marks only a compile.
COMPILE_EVENTS = (
    "trace_to_jaxpr_dynamic",
    "trace_to_jaxpr_nounits",
    "lower_sharding_computation",
    "lower_parallel_callable",
)


def stage_records(stage: str, jobs: int) -> Optional[List]:
    """The traced jobs' records of ``stage``, oldest first, or ``None``."""
    try:
        import repro.utils.spans as spans
    except ImportError:
        return None
    recs = [r for r in spans.records() if r.name == stage]
    if jobs < 1 or len(recs) < jobs:
        return None
    return recs[-jobs:]


def counter(ctx, key: str, stages=STAGES) -> Optional[float]:
    """Counter ``key`` summed over the traced jobs' records of ``stages``."""
    total = 0.0
    for stage in stages:
        recs = stage_records(stage, ctx["jobs"])
        if recs is None:
            return None
        total += sum(r.counters.get(key, 0) for r in recs)
    return total


def busy_in(tr: trace.Trace, name: str) -> Optional[float]:
    """Device-busy seconds inside the benchmark's spans called ``name``,
    averaged over the chips."""
    merged = trace.busy(tr)
    stretches = [(s, e) for n, s, e in tr.spans if n == name]
    if not merged or not any(merged) or not stretches:
        return None
    busy_ns = sum(trace.covered(m, s, e) for m in merged for s, e in stretches)
    return busy_ns / len(merged) * 1e-9


def per_unit_us(ctx, span_name: str, key: str, stage: str) -> Optional[float]:
    """Microseconds of device-busy time in ``span_name`` spans per unit of
    counter ``key`` of the ``stage`` records; ``None`` where either is
    missing or no unit was counted."""
    units = counter(ctx, key, (stage,))
    busy_s = busy_in(ctx["trace"], span_name)
    if not units or busy_s is None:
        return None
    return busy_s / units * 1e6
