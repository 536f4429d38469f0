"""repro.serve: posterior-as-a-service on the chunk stream.

Three layers of coverage, mirroring the package split:

- **state**: ServeState folds == stream_combine's engine (refreshed
  estimates score identically to the trajectory rows), restart-from-
  checkpoint rebuilds bitwise with replayed chunks counted separately and
  never double-folded (extends test_streaming's interrupt→resume contract
  to the serving loop — the satellite);
- **handlers**: the pure query surface — all four posterior ops plus
  status, typed 503 for EstimateUnavailable, 400s for malformed requests
  only (a failing computation raises, and answers 500 over TCP),
  staleness metadata on every response;
- **server**: the asyncio loop end to end — concurrent TCP readers during
  live sampling, monotone staleness counters, chunks never dropped under
  backpressure, clean completion.
"""

import asyncio
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Pipeline, RunSpec
from repro.api.pipeline import resolve_metric
from repro.core.combiners import EstimateUnavailable
from repro.serve import (
    PosteriorServer,
    ServeClient,
    ServeError,
    ServeState,
    answer,
    serve_pipeline,
)

SPEC = RunSpec(
    model="linear", M=4, T=60, warmup=30, n=512, seed=3,
    groundtruth_T=120, combiner=("parametric", "pool", "online"),
    score_metric="logl2", stream_every=20,
)


def _serve_state(pipe, names=None, **kw):
    kw.setdefault("n_estimate", 32)
    return ServeState(
        pipe.stream_setup(names),
        spec_id=pipe.spec.spec_id,
        total_draws=pipe.spec.T,
        **kw,
    )


def _folding_subscriber(state):
    """fold + refresh every chunk — the deterministic (refresh='every')
    folder the bitwise tests drive without an event loop."""

    def on_chunk(ev):
        state.fold(ev)
        state.refresh()

    return on_chunk


# ---------------------------------------------------------------------------
# state: the deterministic core
# ---------------------------------------------------------------------------


def test_serve_state_estimates_are_stream_combine_rows():
    """The serving contract: an estimate refreshed at boundary t scores
    identically to the stream_combine trajectory row at t — same streaming
    state, same fold_in(k_name, t) key, bitwise the same draw cloud."""
    spec = dataclasses.replace(SPEC, combiner=("parametric", "pool"))
    pipe = Pipeline(spec)
    state = _serve_state(pipe, track_history=True)
    pipe.sample(on_chunk=(_folding_subscriber(state),))

    ref_pipe = Pipeline(spec)
    sr = ref_pipe.stream_combine(n_estimate=32, fused=False)
    gt = ref_pipe.groundtruth()
    dist, _ = resolve_metric(spec, ref_pipe._model.d)

    by_row = {(t, name): samples for t, name, samples in state.history}
    assert len(by_row) == len(sr.trajectory)
    for row in sr.trajectory:
        served = by_row[(row["t"], row["combiner"])]
        # jnp.asarray: feed dist the same input type the trajectory used — a
        # numpy operand can select a different-layout executable whose
        # reduction order drifts at the last ulp
        err = float(dist(gt, jnp.asarray(served)))
        assert err == row["error"], (row["t"], row["combiner"])


def test_serve_state_staleness_counters():
    pipe = Pipeline(SPEC)
    state = _serve_state(pipe)
    seen = []
    def on_chunk(ev):
        state.fold(ev)
        seen.append(dict(state.staleness("parametric")))
    pipe.sample(on_chunk=(on_chunk,))
    state.refresh()

    assert [s["draws_seen"] for s in seen] == [20, 40, 60]
    assert [s["chunks_folded"] for s in seen] == [1, 2, 3]
    assert all(s["chunks_replayed"] == 0 for s in seen)
    assert not seen[0]["complete"] and seen[-1]["complete"]
    stamps = [s["last_fold_monotonic_s"] for s in seen]
    assert stamps == sorted(stamps)  # honest per-chunk landed clock
    final = state.staleness("parametric")
    assert final["spec_id"] == SPEC.spec_id
    assert final["estimate_draws_seen"] == 60
    assert final["estimate_age_draws"] == 0


def test_serve_restart_from_checkpoint_is_bitwise(tmp_path):
    """Satellite: kill the serving fold mid-stream, restart from the
    checkpoint dir — replayed chunks are marked, counted separately, never
    double-folded, and every post-restart estimate is bitwise the
    uninterrupted run's."""
    spec = dataclasses.replace(SPEC, combiner=("parametric", "pool", "online"))

    ref_pipe = Pipeline(spec, checkpoint_dir=tmp_path / "ref", checkpoint_every=20)
    ref = _serve_state(ref_pipe, track_history=True)
    ref_pipe.sample(on_chunk=(_folding_subscriber(ref),))
    assert ref.staleness()["complete"]

    # session 1: budget of one chunk, then "killed"
    p1 = Pipeline(spec, checkpoint_dir=tmp_path / "run", checkpoint_every=20)
    s1 = _serve_state(p1, track_history=True)
    p1.sample(max_steps=20, on_chunk=(_folding_subscriber(s1),))
    st1 = s1.staleness()
    assert st1["draws_seen"] == 20 and not st1["complete"]

    # session 2: fresh server state, resumes from the checkpoint — the
    # restored prefix arrives as replayed=True chunks and rebuilds state
    p2 = Pipeline(spec, checkpoint_dir=tmp_path / "run", checkpoint_every=20)
    s2 = _serve_state(p2, track_history=True)
    p2.sample(on_chunk=(_folding_subscriber(s2),))

    st2 = s2.staleness()
    assert st2["complete"] and st2["draws_seen"] == spec.T
    assert st2["chunks_replayed"] == 1  # the restored 1-chunk prefix
    assert st2["chunks_folded"] == spec.T // spec.stream_every  # no double-fold
    # every refreshed estimate bitwise-matches the uninterrupted run
    assert [(t, n) for t, n, _ in s2.history] == [(t, n) for t, n, _ in ref.history]
    for (t, name, got), (_, _, want) in zip(s2.history, ref.history):
        np.testing.assert_array_equal(got, want, err_msg=f"{name}@{t}")
    for name in spec.combiner_names():
        np.testing.assert_array_equal(
            s2.snapshot(name).samples, ref.snapshot(name).samples, err_msg=name
        )


# ---------------------------------------------------------------------------
# handlers: the pure query surface
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def folded_state():
    spec = dataclasses.replace(SPEC, combiner=("parametric", "pool", "consensus"))
    pipe = Pipeline(spec)
    state = _serve_state(pipe)
    pipe.sample(on_chunk=(_folding_subscriber(state),))
    return state


def test_answer_mean_cov_quantiles_draws(folded_state):
    d = folded_state.snapshot("parametric").samples.shape[1]
    for name in ("parametric", "pool"):
        r = answer(folded_state, {"op": "mean_cov", "combiner": name})
        assert r["ok"], r
        assert len(r["result"]["mean"]) == d
        assert len(r["result"]["cov"]) == d and len(r["result"]["cov"][0]) == d
        assert r["staleness"]["draws_seen"] == SPEC.T
        assert r["staleness"]["spec_id"] == folded_state.spec_id

    q = answer(folded_state, {"op": "quantiles", "probs": [0.1, 0.5, 0.9]})
    assert q["ok"] and np.asarray(q["result"]["quantiles"]).shape == (3, d)
    med = np.asarray(q["result"]["quantiles"])[1]
    lo, hi = np.asarray(q["result"]["quantiles"])[0], np.asarray(q["result"]["quantiles"])[2]
    assert np.all(lo <= med) and np.all(med <= hi)

    d1 = answer(folded_state, {"op": "draws", "n": 5, "seed": 7})
    d2 = answer(folded_state, {"op": "draws", "n": 5, "seed": 7})
    assert d1["result"]["draws"] == d2["result"]["draws"]  # deterministic
    assert np.asarray(d1["result"]["draws"]).shape == (5, d)
    # "predictive" is an alias
    assert answer(folded_state, {"op": "predictive", "n": 3})["ok"]


def test_answer_logpdf_matches_direct_scoring(folded_state):
    from repro.core.combiners import counts_or_full
    from repro.core.combiners.density import machine_kde_scores, masked_silverman

    snap = folded_state.snapshot("parametric")
    pts = [snap.mean.tolist(), (snap.mean + 1.0).tolist()]
    r = answer(folded_state, {"op": "logpdf", "points": pts})
    assert r["ok"], r
    got = np.asarray(r["result"]["log_density"])
    assert got.shape == (2,) and np.all(np.isfinite(got))
    assert got[0] > got[1]  # the posterior mean outscores an offset point

    theta, counts = folded_state.logpdf_inputs()
    h = masked_silverman(theta, counts_or_full(theta, counts))
    want = machine_kde_scores(
        jnp.asarray(np.asarray(pts, np.float32)), theta, counts, h,
        reduce="product",
    )
    np.testing.assert_array_equal(got, np.asarray(want))
    assert r["result"]["normalized"] is False


def test_answer_maps_estimate_unavailable_to_503(folded_state):
    r = answer(folded_state, {"op": "mean_cov", "combiner": "consensus"})
    assert not r["ok"]
    assert r["error"]["code"] == 503
    assert "estimate" in r["error"]["reason"]
    assert r["staleness"]["draws_seen"] == SPEC.T  # 503s still say where we are


def test_answer_rejects_malformed_requests(folded_state):
    assert answer(folded_state, {"op": "nope"})["error"]["code"] == 400
    assert answer(
        folded_state, {"op": "mean_cov", "combiner": "no_such"}
    )["error"]["code"] == 400
    assert answer(folded_state, {"op": "logpdf"})["error"]["code"] == 400
    assert answer(
        folded_state, {"op": "quantiles", "probs": [1.5]}
    )["error"]["code"] == 400
    assert answer(folded_state, {"op": "draws", "n": 0})["error"]["code"] == 400


def _refused(*_args, **_kwargs):
    # a kernel the TPU compiler refuses surfaces as a plain ValueError
    raise ValueError("Mosaic failed to compile TPU kernel")


def test_answer_raises_compute_failures_instead_of_400(folded_state, monkeypatch):
    """A computation that fails is the server's fault: ``answer`` raises it
    instead of reporting the request as malformed."""
    from repro.serve import handlers

    monkeypatch.setattr(handlers, "machine_kde_scores", _refused)
    with pytest.raises(ValueError, match="Mosaic"):
        answer(folded_state, {"op": "logpdf", "points": [[0.0] * 10]})
    # validation still answers 400 on the same state
    assert answer(folded_state, {"op": "logpdf"})["error"]["code"] == 400


def test_answer_before_any_fold_is_503_with_position():
    pipe = Pipeline(SPEC)
    state = _serve_state(pipe)
    r = answer(state, {"op": "mean_cov"})
    assert not r["ok"] and r["error"]["code"] == 503
    assert r["staleness"]["draws_seen"] == 0 and not r["staleness"]["complete"]
    assert answer(state, {"op": "status"})["ok"]  # status needs no estimate


def test_serve_state_typed_unavailability():
    pipe = Pipeline(dataclasses.replace(SPEC, combiner=("consensus",)))
    state = _serve_state(pipe, keep_draws=False)
    with pytest.raises(EstimateUnavailable):
        state.snapshot("consensus")
    with pytest.raises(EstimateUnavailable, match="keep_draws"):
        state.logpdf_inputs()
    with pytest.raises(KeyError, match="not served"):
        state.snapshot("parametric")


# ---------------------------------------------------------------------------
# server: the asyncio loop
# ---------------------------------------------------------------------------


def test_server_concurrent_queries_during_sampling():
    """All four posterior query types answered over TCP while the chains
    extend, staleness on every response and monotone per connection."""
    spec = dataclasses.replace(SPEC, combiner=("parametric", "online"))

    async def main():
        server = PosteriorServer(Pipeline(spec), refresh="every", queue_depth=2)
        await server.start()

        async def reader(idx):
            client = await ServeClient.connect(server.host, server.port)
            ops = (
                {"op": "mean_cov", "combiner": "online"},
                {"op": "quantiles"},
                {"op": "draws", "n": 4},
                {"op": "logpdf", "points": [[0.0] * 10]},
            )
            last = (-1, -1)
            answered = 0
            try:
                while not server._complete.is_set():
                    resp = await client.request(**ops[(answered + idx) % len(ops)])
                    st = resp["staleness"]
                    now = (st["chunks_folded"], st["draws_seen"])
                    assert now >= last, (last, now)
                    last = now
                    if resp["ok"]:
                        answered += 1
                    else:
                        assert resp["error"]["code"] == 503, resp
            finally:
                await client.close()
            return answered

        readers = [asyncio.create_task(reader(i)) for i in range(6)]
        await server.wait_complete()
        answered = sum(await asyncio.gather(*readers))
        # completed posterior answers everything
        for op in ("mean_cov", "quantiles", "draws", "logpdf", "status"):
            params = {"points": [[0.0] * 10]} if op == "logpdf" else {}
            resp = await server.query(op, **params)
            assert resp["ok"], resp
            assert resp["staleness"]["complete"]
        st = server.state.staleness()
        await server.stop()
        return answered, st

    answered, st = asyncio.run(main())
    assert st["chunks_folded"] == spec.T // spec.stream_every  # never dropped
    assert st["draws_seen"] == spec.T and st["complete"]
    assert answered >= 0  # mid-stream answers are timing-dependent; 503s ok


def test_serve_pipeline_summary_and_backpressure():
    """The sync driver (mcmc_run --serve / CI smoke): probes assert
    monotone staleness internally; chunks are never dropped even at
    queue_depth=1 with refresh coalescing; the final snapshot is fresh."""
    spec = dataclasses.replace(SPEC, combiner=("parametric",))
    summary = serve_pipeline(
        Pipeline(spec), probe_readers=3, queue_depth=1,
        probe_logpdf=True, log=lambda *_: None,
    )
    st = summary["staleness"]
    assert st["chunks_folded"] == spec.T // spec.stream_every
    assert st["draws_seen"] == spec.T and st["complete"]
    assert st["refreshes_dropped"] >= 0
    assert st["estimate_draws_seen"] == spec.T  # final refresh always lands
    assert summary["queries"] > 0
    assert summary["probe_errors"] == []
    for op in ("mean_cov", "quantiles", "draws", "status", "logpdf"):
        assert summary["final"][op]["ok"], op


def test_server_requires_stream_cadence_and_valid_options():
    spec = dataclasses.replace(SPEC, stream_every=0)
    with pytest.raises(ValueError, match="stream_every"):
        PosteriorServer(Pipeline(spec))
    with pytest.raises(ValueError, match="refresh"):
        PosteriorServer(Pipeline(SPEC), refresh="sometimes")
    with pytest.raises(ValueError, match="queue_depth"):
        PosteriorServer(Pipeline(SPEC), queue_depth=0)


def test_client_ask_raises_typed_serve_error():
    spec = dataclasses.replace(SPEC, combiner=("parametric", "consensus"))

    async def main():
        server = PosteriorServer(Pipeline(spec), refresh="every")
        await server.start()
        await server.wait_complete()
        client = await ServeClient.connect(server.host, server.port)
        try:
            result = await client.ask("mean_cov", combiner="parametric")
            assert len(result["mean"]) == 10
            with pytest.raises(ServeError) as exc:
                await client.ask("mean_cov", combiner="consensus")
            assert exc.value.code == 503
            assert exc.value.staleness["complete"]
        finally:
            await client.close()
            await server.stop()

    asyncio.run(main())


def test_server_reports_compute_failure_as_500(monkeypatch):
    """Over TCP a failing computation answers 500, not a client-error 400,
    and the connection keeps serving."""
    from repro.serve import handlers

    monkeypatch.setattr(handlers, "machine_kde_scores", _refused)
    spec = dataclasses.replace(SPEC, combiner=("parametric",))

    async def main():
        server = PosteriorServer(Pipeline(spec), refresh="every")
        await server.start()
        await server.wait_complete()
        client = await ServeClient.connect(server.host, server.port)
        try:
            resp = await client.request("logpdf", points=[[0.0] * 10])
            assert not resp["ok"]
            assert resp["error"]["code"] == 500
            assert "Mosaic" in resp["error"]["reason"]
            assert (await client.request("status"))["ok"]
        finally:
            await client.close()
            await server.stop()

    asyncio.run(main())


def test_server_answers_a_logpdf_batch_beyond_64k():
    """A logpdf batch travels as one protocol line; 2048 points at d=10 is
    several hundred KiB, past asyncio's default 64 KiB line limit."""
    spec = dataclasses.replace(SPEC, combiner=("parametric",))
    points = np.random.default_rng(0).normal(size=(2048, 10)).tolist()

    async def main():
        server = PosteriorServer(Pipeline(spec), refresh="every")
        await server.start()
        await server.wait_complete()
        client = await ServeClient.connect(server.host, server.port)
        try:
            result = await client.ask("logpdf", points=points)
            assert len(result["log_density"]) == 2048
        finally:
            await client.close()
            await server.stop()

    asyncio.run(main())
