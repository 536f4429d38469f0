"""Program spans and executable counters (``repro.utils.spans``)."""

from __future__ import annotations

import sys
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.api import Pipeline, RunSpec
from repro.api.pipeline import combine_spec_draws
from repro.api.streaming import stream_sample
from repro.core.subposterior import partition_data
from repro.models.bayes import get_model
from repro.utils import spans


def _names_since(before):
    """Names of the spans closed after the last record of ``before``."""
    recs = spans.records()
    start = 0
    if before:
        start = next(i for i in range(len(recs) - 1, -1, -1) if recs[i] is before[-1]) + 1
    return [r.name for r in recs[start:]]


def test_first_call_of_a_fresh_jit_counts_an_executable():
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + 0.25)
    x = jnp.arange(7.0)
    with spans.span("first") as first:
        f(x).block_until_ready()
    with spans.span("second") as second:
        f(x).block_until_ready()
    assert first.counters["executables"] >= 1
    assert first.counters["backend_compile_s"] > 0
    assert second.counters.get("executables", 0) == 0
    assert second.counters.get("backend_compile_s", 0) == 0
    assert first.parent is None and 0 < first.start_ns < first.end_ns <= second.start_ns
    assert spans.records()[-2:] == (first, second)


def test_nested_spans_charge_the_innermost_and_roll_up_on_exit():
    f = jax.jit(lambda x: jnp.cos(x) - 0.5)
    with spans.span("outer") as outer:
        spans.count("rows", 3)
        with spans.span("inner.a") as a:
            f(jnp.ones(5)).block_until_ready()
            spans.count("rows", 2)
        with spans.span("inner.b") as b:
            pass
    assert a.parent == b.parent == "outer"
    assert a.counters["executables"] >= 1 and a.counters["rows"] == 2
    assert b.counters == {}
    # the outer span covers what its children counted, and its own
    assert outer.counters["rows"] == 5
    assert outer.counters["executables"] == a.counters["executables"]
    assert outer.counters["backend_compile_s"] == a.counters["backend_compile_s"]
    recs = spans.records()
    assert [r.name for r in recs[-3:]] == ["inner.a", "inner.b", "outer"]


def test_counts_outside_any_span_are_dropped():
    before = spans.records()
    spans.count("rows", 4)
    jax.jit(lambda x: x * 7.0 + 2.0)(jnp.ones(3)).block_until_ready()
    assert spans.records() == before


def test_the_ring_stays_bounded():
    for i in range(spans.RING_SIZE + 25):
        with spans.span("ring"):
            spans.count("i", i)
    recs = spans.records()
    assert len(recs) == spans.RING_SIZE
    assert recs[-1].counters["i"] == spans.RING_SIZE + 24
    assert recs[0].counters["i"] == 25


def test_each_thread_nests_its_own_spans():
    """More threads than cores, switching often: every record's parent and
    counters come from its own thread, and no count is lost."""
    n_threads, n_spans = 16, 200
    errors = []

    def work(t):
        try:
            for _ in range(n_spans):
                with spans.span(f"t{t}") as outer:
                    with spans.span(f"t{t}.in") as inner:
                        spans.count("n", 1)
                    if inner.parent != f"t{t}" or outer.counters != {"n": 1}:
                        errors.append((t, inner.parent, outer.counters))
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append((t, repr(e)))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(t,)) for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in threads)
    assert errors == []


def test_a_stage_called_twice_leaves_the_same_spans():
    """Spans sit in host code: a span inside traced code would appear on the
    first call, while JAX traces, and not on the second."""
    model = get_model("logreg")
    data, _ = model.generate_data(jax.random.PRNGKey(0), 400)
    shards, counts = partition_data(data, 2, only=model.shard_keys, pad=True)
    spec = RunSpec(model="logreg", M=2, T=40, warmup=10, n=400,
                   combiner=("parametric", "semiparametric")).validate()

    def job(j):
        key = jax.random.PRNGKey(j)
        res = stream_sample(key, model, data, 2, 40, warmup=10, burn_in=6,
                            shards=shards, counts=counts).result
        out = combine_spec_draws(spec, key, res.theta)
        jax.block_until_ready(out)

    names = []
    for j in range(2):
        before = spans.records()
        job(j)
        names.append(_names_since(before))
    assert names[0] == names[1] == [
        "sample.chunk", "sample.stage",
        "combine.parametric",
        "combine.img.model", "combine.img.chain", "combine.semiparametric",
        "combine.stage",
    ]
    recs = spans.records()
    stage = [r for r in recs if r.name == "sample.stage"][-1]
    assert stage.counters["steps"] == 10 + 6 + 40  # warmup + burn-in + T
    chain = [r for r in recs if r.name == "combine.img.chain"][-1]
    assert chain.counters["img_sites"] == 40 * 1 * 2  # sweeps x chains x M
    combine = [r for r in recs if r.name == "combine.stage"][-1]
    assert combine.counters["img_sites"] == chain.counters["img_sites"]


@pytest.mark.parametrize("stream", [False, True], ids=["combine", "stream_combine"])
def test_pipeline_timings_read_the_pipeline_spans(stream):
    spec = RunSpec(model="poisson", sampler="rwmh", M=2, T=40, warmup=10, n=200,
                   seed=4, groundtruth_T=60, combiner=("parametric",),
                   stream_every=20 if stream else 0)
    pipe = Pipeline(spec)
    if stream:
        pipe.stream_combine(n_estimate=8)
    board = pipe.run()
    t = board.timings
    mine = {r.name: r for r in spans.records() if r.name.startswith("pipeline.")}
    expected = {"sample_s", "groundtruth_s", "combine_s"}
    if stream:
        expected.add("stream_combine_s")
        assert t["combine_s"] == t["stream_combine_s"]
    assert set(t) == expected
    assert all(v > 0 for v in t.values())
    assert t["sample_s"] == mine["pipeline.sample"].seconds
    assert t["groundtruth_s"] == mine["pipeline.groundtruth"].seconds


def test_a_smoke_phase_counts_the_compiles_of_other_threads():
    """``chip_smoke.py`` charges a phase what the spans of other threads
    (the server's sampler and executor) compiled while it ran."""
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    import chip_smoke

    def worker():
        with spans.span("worker"):
            jax.jit(lambda x: x * 5.0 - 1.5)(jnp.ones(6)).block_until_ready()

    with spans.span("smoke.phase") as phase:
        th = threading.Thread(target=worker)
        th.start()
        th.join()
    later = threading.Thread(target=worker)
    later.start()
    later.join()
    workers = [r for r in spans.records() if r.name == "worker"][-2:]
    assert phase.counters == {}  # nothing compiled on the phase's own thread
    assert workers[0].counters["backend_compile_s"] > 0
    assert chip_smoke._compile_s(phase) == workers[0].counters["backend_compile_s"]
