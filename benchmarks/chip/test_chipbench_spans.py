"""The metrics that read the program's own spans and counters.

Hand-made traces and records check the arithmetic of the four readers; one
tiny semiparametric job, traced on the CPU, checks that the program's spans
land where the readers look for them and leave the benchmark's own spans,
and the metrics that read those, as they were.
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench import cell, harness, program, trace  # noqa: E402
from chipbench.jobs import Jobs, seed_key  # noqa: E402
from repro.utils import spans  # noqa: E402

NEW = ("compile_s", "executables", "sample_step_us", "img_site_us")

# one job on one chip: sample 0..100 (busy 10..50), combine 100..300 (busy
# 150..250); JAX traced and lowered 120..140 and 130..160 inside
# combine.stage, and 20..25 inside sample.stage
HAND = trace.Trace(
    spans=[("job", 0.0, 300.0), ("sample", 0.0, 100.0), ("combine", 100.0, 300.0)],
    host=[("job", 0.0, 300.0), ("sample", 0.0, 100.0),
          ("sample.stage", 1.0, 99.0), ("lower_sharding_computation", 20.0, 25.0),
          ("combine", 100.0, 300.0), ("combine.stage", 101.0, 299.0),
          ("combine.img.chain", 110.0, 290.0),
          ("trace_to_jaxpr_dynamic", 120.0, 140.0),
          ("lower_sharding_computation", 130.0, 160.0),
          ("PjitFunction(scan)", 115.0, 280.0),
          ("trace_to_jaxpr_dynamic", 305.0, 310.0)],  # outside every stage
    devices=[[("while.4", 10.0, 50.0), ("while.27", 150.0, 250.0)]],
)
RECORDS = (
    spans.Span("sample.stage", None, 0, 10, {"steps": 20}),
    spans.Span("combine.img.chain", "combine.semiparametric", 12, 20,
               {"img_sites": 50, "executables": 1, "backend_compile_s": 0.5}),
    spans.Span("combine.stage", None, 11, 22,
               {"img_sites": 50, "executables": 1, "backend_compile_s": 0.5}),
)


def _ctx(tr=HAND, jobs=1):
    return {"trace": tr, "cell": None, "jobs": jobs, "peak": None}


def _read(name, ctx):
    return cell.reader(name).read(ctx)


def test_readers_by_hand(monkeypatch):
    # an older job's records come first; the readers take the last ones
    old = (spans.Span("sample.stage", None, 0, 1, {"steps": 999}),
           spans.Span("combine.stage", None, 1, 2, {"executables": 7}))
    monkeypatch.setattr(spans, "records", lambda: old + RECORDS)
    ctx = _ctx()
    # union of 20..25 and 120..160 inside the stages: 45 ns, plus 0.5 s read
    assert _read("compile_s", ctx) == pytest.approx(0.5 + 45e-9)
    assert _read("executables", ctx) == 1.0
    # 40 ns busy in sample over 20 steps; 100 ns busy in combine over 50 sites
    assert _read("sample_step_us", ctx) == pytest.approx(40e-9 / 20 * 1e6)
    assert _read("img_site_us", ctx) == pytest.approx(100e-9 / 50 * 1e6)
    # two jobs take both records of each stage
    assert _read("executables", _ctx(jobs=2)) == pytest.approx(8 / 2)


def test_no_such_work_reads_zero_and_no_count_reads_nothing(monkeypatch):
    quiet = (spans.Span("sample.stage", None, 0, 10, {"steps": 20}),
             spans.Span("combine.stage", None, 11, 22, {}))
    monkeypatch.setattr(spans, "records", lambda: quiet)
    bare = HAND._replace(host=[e for e in HAND.host if e[0] not in program.COMPILE_EVENTS])
    assert _read("compile_s", _ctx(bare)) == 0.0
    assert _read("executables", _ctx(bare)) == 0.0
    assert _read("img_site_us", _ctx(bare)) is None  # no site to divide by
    assert _read("sample_step_us", _ctx(HAND._replace(devices=[]))) is None


@pytest.mark.parametrize("name", NEW)
def test_readers_read_nothing_without_the_program_spans(monkeypatch, name):
    monkeypatch.setattr(spans, "records", lambda: ())
    assert _read(name, _ctx()) is None
    # a program that has no repro.utils.spans at all
    monkeypatch.setattr(spans, "records", lambda: RECORDS)
    monkeypatch.setitem(sys.modules, "repro.utils.spans", None)
    assert _read(name, _ctx()) is None


def test_too_few_records_for_the_traced_jobs_read_nothing(monkeypatch):
    monkeypatch.setattr(spans, "records", lambda: RECORDS)
    for name in NEW:
        assert _read(name, _ctx(jobs=2)) is None


@pytest.fixture(scope="module")
def traced_job():
    """One tiny semiparametric job under the profiler that has to obtain its
    programs anew: a warm-up job, then JAX's in-memory caches cleared."""
    import jax

    c = cell.find("logreg-paper.batch-semiparametric")
    cfg = dict(c.config, N=800, M=2, T=60, warmup=10, burn_in=10)
    key = seed_key(2**33 + 12345)
    data = c.model.make_data(jax.random.fold_in(key, 0), cfg)
    jobs = Jobs(c._replace(config=cfg, chips=1), data, key)
    jobs.run(0)
    jax.clear_caches()
    _, tr = harness._traced(jobs, 1, 1, 1, None)
    return tr


def test_program_spans_nest_inside_the_benchmark_spans(traced_job):
    tr = traced_job
    names = [n for n, _, _ in tr.host]
    mine = {"sample.stage", "sample.chunk", "combine.stage", "combine.semiparametric",
            "combine.img.model", "combine.img.chain"}
    assert mine <= set(names)
    assert not mine & set(trace.SPAN_NAMES)
    assert [n for n, _, _ in tr.spans] == ["job", "sample", "combine"]
    bench = {n: (s, e) for n, s, e in tr.spans}
    for n, s, e in tr.host:
        if n in mine:
            lo, hi = bench[n.split(".")[0]]
            assert lo <= s <= e <= hi, n
    # the IMG chain's re-trace happens inside combine.img.chain
    chain = [(s, e) for n, s, e in tr.host if n == "combine.img.chain"]
    traced = [(s, e) for n, s, e in tr.host if n == "trace_to_jaxpr_dynamic"]
    assert any(cs <= s and e <= ce for s, e in traced for cs, ce in chain)


def test_compile_s_reads_the_traced_job_and_old_readers_do_not_move(traced_job):
    tr = traced_job
    ctx = _ctx(tr)
    assert _read("compile_s", ctx) > 0
    assert _read("executables", ctx) >= 1
    without = tr._replace(host=[e for e in tr.host if "." not in e[0]
                                or e[0].split(".")[0] not in ("sample", "combine")])
    assert len(without.host) < len(tr.host)
    for name in ("sample_s", "combine_s"):
        assert _read(name, ctx) == _read(name, _ctx(without))
