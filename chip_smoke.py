#!/usr/bin/env python3
"""Run the EP-MCMC main path once on a TPU and check what comes out.

    python3 chip_smoke.py               # one chip: phases a-e below
    python3 chip_smoke.py --four-chips  # the 4-chip mesh path, nothing else

One process, no children: JAX gives a chip to one process at a time. Data
are the logistic-regression model's own (paper §8.1: N=50,000 rows, d=50),
made from the spec seed.

a. Device check: the first device must be a TPU and the Pallas kernels must
   run compiled (``repro.kernels.default_interpret()`` False). There is no
   CPU fallback.
b. Batch run: ``mcmc_run --model logreg --M 10 --combiner all``. Every
   combiner's error must be finite and within ``BOUND_FACTOR`` of the CPU
   rehearsal below.
c. Fused stream: the same spec with ``--stream-every 200 --combiner online``,
   so the ``online_update`` kernel runs inside the fused scan.
d. Serve: a ``PosteriorServer`` on a ``stream_every=200`` run answers
   ``mean_cov`` / ``quantiles`` / ``status`` and a 256-point ``logpdf`` over
   TCP; the ``logpdf`` answer is checked against the jnp reference.
e. Kernel parity: each Pallas kernel against its reference at phase b's
   widths, under the tolerance its interpret-mode test pins, and the
   compiled program of each must hold the Mosaic kernel (``tpu_custom_call``).

``--four-chips`` runs logreg with M=20 on a (4, 1) mesh and the same spec
on one device (mesh (1, 1)), in this process, and holds them to the
contract ``tests/test_mesh_stream.py`` pins for the fused stream.

Each phase runs inside a ``smoke.<phase>`` span and prints one JSON line
with its wall time, which ends after the results are on the host, and the
XLA compile seconds (executables compiled or read from the cache) counted
by its span and by the spans other threads ran meanwhile, reported apart.
A failed phase makes the script exit 1. The last line of standard output
is ``{"ok": true, "device": {...}}`` only when every phase passed.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import sys
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# CPU rehearsal of phases b and c (XLA CPU, jaxlib 0.9.0): the largest
# logL2 error of eight runs on seed 0's data, one with the spec's own keys
# and seven with the sampling, groundtruth and combiner keys replaced. The
# chip's chains are one more such run: float rounding differs from the CPU's,
# so accept/reject decisions and draws part ways, and the error is a fresh
# Monte Carlo realization (the eight spread by up to 4.7 in log space). The
# scoreboard reports log d₂, so "within 1.5× of the CPU error" is
# err ≤ cpu_err + log 1.5.
CPU_ERRORS_BATCH = {
    "consensus": 65.5597, "importance_pool": 67.3305, "nonparametric": 64.9314,
    "online": 65.2906, "parametric": 65.2461, "pool": 64.9313, "rpt": 64.9313,
    "semiparametric": 72.2889, "semiparametric_w": 72.9696,
    "subpost_average": 64.9633, "weierstrass": 64.9313,
}
CPU_ERROR_STREAM_ONLINE = 65.2906
BOUND_FACTOR = 1.5

BATCH_ARGS = ["--model", "logreg", "--M", "10", "--samples", "2000",
              "--groundtruth-samples", "4000"]
STREAM_EVERY = 200
LOGPDF_POINTS = 256

# kernel ≡ ref tolerances, as the interpret-mode tests pin them
TOL_KDE = dict(rtol=1e-5, atol=1e-4)  # test_machine_kde.py, test_kernels.py
TOL_IMG = dict(rtol=2e-5, atol=5e-3)  # test_kernels.py (float32)
TOL_ONLINE = {"count": dict(rtol=1e-6, atol=0.0),  # test_fused_stream.py
              "mean": dict(rtol=1e-5, atol=1e-5),
              "m2": dict(rtol=1e-4, atol=1e-4)}
TOL_LOGREG = {"loglik": dict(rtol=1e-5, atol=0.0),  # test_kernels.py
              "grad": dict(rtol=1e-4, atol=1e-3)}


def _stop(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def _compile_s(phase):
    """Compile seconds of ``phase``'s span and of the outermost spans that
    other threads (the server's sampler and executor) opened and closed
    inside it; the phase itself is the only outermost span of its thread."""
    from repro.utils.spans import records

    return sum(r.counters.get("backend_compile_s", 0.0) for r in records()
               if r.parent is None
               and phase.start_ns <= r.start_ns and r.end_ns <= phase.end_ns)


def _close(name, got, want, rtol, atol):
    got, want = np.asarray(got), np.asarray(want)
    both_inf = np.isneginf(got) & np.isneginf(want)
    got, want = np.where(both_inf, 0.0, got), np.where(both_inf, 0.0, want)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        raise AssertionError(f"{name}: shape {got.shape} vs {want.shape} or non-finite")
    excess = np.abs(got - want) - (atol + rtol * np.abs(want))
    worst = float(np.max(excess))
    if worst > 0:
        raise AssertionError(f"{name}: off by {worst:.3g} beyond rtol={rtol} atol={atol}")
    return float(np.max(np.abs(got - want)))


def _kde_f64(queries, samples, h, counts):
    """(M, Q) per-machine KDE log densities in float64 on the host."""
    q = np.asarray(queries, np.float64)
    rows = []
    for s, hm, c in zip(np.asarray(samples, np.float64), np.asarray(h, np.float64),
                        np.asarray(counts)):
        s = s[:c]
        shift = s.mean(0)  # f64 and shifted: the expansion loses nothing here
        qs, ss = q - shift, s - shift
        sq = (qs**2).sum(1)[:, None] + (ss**2).sum(1)[None, :] - 2.0 * qs @ ss.T
        logk = -0.5 * sq / hm**2
        top = logk.max(1)
        lse = top + np.log(np.exp(logk - top[:, None]).sum(1))
        rows.append(lse - math.log(c) - 0.5 * q.shape[1] * math.log(2 * math.pi * hm**2))
    return np.stack(rows)


def _within_cpu(name, err, cpu_err):
    if not math.isfinite(err):
        raise AssertionError(f"{name}: error {err} is not finite")
    if cpu_err is not None and err > cpu_err + math.log(BOUND_FACTOR):
        raise AssertionError(
            f"{name}: logL2 {err:.4f} > CPU {cpu_err:.4f} + log {BOUND_FACTOR}"
        )


# -- phases -----------------------------------------------------------------


def phase_batch():
    from repro.launch import mcmc_run

    errors = mcmc_run.main(BATCH_ARGS + ["--combiner", "all"])
    if set(errors) != set(CPU_ERRORS_BATCH):
        raise AssertionError(f"combiners {sorted(errors)} != {sorted(CPU_ERRORS_BATCH)}")
    for name, err in errors.items():
        _within_cpu(name, err, CPU_ERRORS_BATCH[name])
    return {"errors": errors}


def phase_stream():
    from repro.launch import mcmc_run

    errors = mcmc_run.main(
        BATCH_ARGS + ["--stream-every", str(STREAM_EVERY), "--combiner", "online"]
    )
    _within_cpu("online", errors["online"], CPU_ERROR_STREAM_ONLINE)
    return {"errors": errors}


def phase_serve():
    from repro.api import Pipeline, RunSpec
    from repro.core.combiners import counts_or_full
    from repro.core.combiners.density import masked_silverman
    from repro.kernels.kde_density import machine_kde_log_density_ref
    from repro.serve import PosteriorServer, ServeClient

    spec = RunSpec(model="logreg", M=10, T=2000, groundtruth_T=4000,
                   stream_every=STREAM_EVERY, combiner=("parametric", "online"))

    async def session():
        server = PosteriorServer(Pipeline(spec), refresh="every")
        await server.start()
        client = await ServeClient.connect(server.host, server.port)
        mid = 0
        try:
            while not server._complete.is_set():  # a reader during sampling
                resp = await client.request("mean_cov")
                if not resp["ok"] and resp["error"]["code"] != 503:
                    raise AssertionError(f"mid-stream mean_cov: {resp}")
                mid += 1
                await asyncio.sleep(0.05)
            await server.wait_complete()
            theta, counts = server.state.logpdf_inputs()
            d = theta.shape[-1]
            stride = theta.shape[0] * theta.shape[1] // LOGPDF_POINTS
            points = np.asarray(theta).reshape(-1, d)[::stride][:LOGPDF_POINTS]
            answers = {
                "mean_cov_parametric": await client.request("mean_cov", combiner="parametric"),
                "mean_cov_online": await client.request("mean_cov", combiner="online"),
                "quantiles": await client.request("quantiles"),
                "status": await client.request("status"),
                "logpdf": await client.request("logpdf", points=points.tolist()),
            }
        finally:
            await client.close()
            await server.stop()
        return answers, theta, counts, points, mid

    answers, theta, counts, points, mid = asyncio.run(session())
    for key, resp in answers.items():
        if not resp["ok"]:
            raise AssertionError(f"{key}: {resp['error']}")
    d = theta.shape[-1]
    for key in ("mean_cov_parametric", "mean_cov_online"):
        mean = np.asarray(answers[key]["result"]["mean"])
        if mean.shape != (d,) or not np.all(np.isfinite(mean)):
            raise AssertionError(f"{key}: mean {mean.shape} not finite (d={d})")
    q = np.asarray(answers["quantiles"]["result"]["quantiles"])
    if q.shape != (5, d) or not np.all(np.diff(q, axis=0) >= 0):
        raise AssertionError(f"quantiles: shape {q.shape} or not monotone")
    got = np.asarray(answers["logpdf"]["result"]["log_density"])
    h = masked_silverman(theta, counts_or_full(theta, counts))
    # the reference at the same shift the kernel path applies (the points'
    # mean): unshifted, f32 cancellation at posterior draws' norms moves it
    # by more than the tolerance on its own
    center = jnp.mean(jnp.asarray(points), axis=0)
    want = machine_kde_log_density_ref(
        jnp.asarray(points) - center, theta - center, h, counts, reduce="product"
    )
    maxabs = _close("logpdf", got, want, **TOL_KDE)
    return {"mid_stream_queries": mid, "logpdf_points": int(got.shape[0]),
            "logpdf_maxabs_vs_ref": maxabs}


def phase_kernels():
    from repro.kernels.img_weights import img_log_weights, img_log_weights_ref
    from repro.kernels.kde_density import (
        kde_log_density,
        kde_log_density_ref,
        machine_kde_log_density,
        machine_kde_log_density_ref,
    )
    from repro.kernels.logreg_loglik import (
        lane_dense,
        logreg_loglik_grad,
        logreg_loglik_grad_ref,
    )
    from repro.kernels.online_update import online_moments_update
    from repro.kernels.online_update.ref import online_moments_update_ref

    M, T, Q, d, C, N = 10, 2000, 2000, 50, STREAM_EVERY, 5000
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    samples = jax.random.normal(ks[0], (M, T, d))
    queries = jax.random.normal(ks[1], (Q, d))
    h = jnp.abs(jax.random.normal(ks[2], (M,))) * 0.4 + 0.2
    counts = jax.random.randint(ks[3], (M,), 1, T + 1).at[0].set(T)

    def run(fn, *args):
        """Result on the host, plus whether the compiled program holds the
        Mosaic kernel."""
        compiled = jax.jit(fn).lower(*args).compile()
        got = jax.device_get(jax.block_until_ready(compiled(*args)))
        return got, "tpu_custom_call" in compiled.as_text()

    def ref(fn, *args):
        with jax.default_matmul_precision("highest"):
            return jax.device_get(jax.jit(fn)(*args))

    out, failed, no_kernel = {}, [], []

    def check(name, got_and_flag, want, tol):
        """Records the largest difference; every kernel is checked before
        the phase fails."""
        got, has_kernel = got_and_flag
        if not has_kernel:
            no_kernel.append(name)
        got = got if isinstance(got, tuple) else (got,)
        want = want if isinstance(want, tuple) else (want,)
        tols = tol if isinstance(tol, list) else [tol] * len(got)
        try:
            out[name] = max(_close(f"{name}[{i}]", g, w, **t)
                            for i, (g, w, t) in enumerate(zip(got, want, tols)))
        except AssertionError as e:
            failed.append(str(e))

    per_machine = {}
    for reduce in ("none", "product", "mixture", "product_mixture"):
        per_machine[reduce] = (
            run(lambda q, s, hh, c, r=reduce: machine_kde_log_density(
                q, s, hh, c, reduce=r, impl="kernel"), queries, samples, h, counts),
            ref(lambda q, s, hh, c, r=reduce: machine_kde_log_density_ref(
                q, s, hh, c, reduce=r), queries, samples, h, counts),
        )
        check(f"machine_kde_{reduce}", *per_machine[reduce], TOL_KDE)
    # information, not a check: how far each side sits from float64
    exact = _kde_f64(queries, samples, h, counts)
    (got_none, _), want_none = per_machine["none"]
    f64_maxabs = {"kernel": float(np.max(np.abs(got_none - exact))),
                  "ref": float(np.max(np.abs(want_none - exact)))}
    check("kde_log_density",
          run(kde_log_density, queries, samples[0], h[0]),
          ref(kde_log_density_ref, queries, samples[0], h[0]), TOL_KDE)

    theta = jax.random.normal(ks[4], (T, M, d))
    check("img_weights", run(img_log_weights, theta, h[0]),
          ref(img_log_weights_ref, theta, h[0]), TOL_IMG)

    chunk = jax.random.normal(ks[5], (M, C, d)) + 2.0
    state = (jnp.full((M,), 400.0), jax.random.normal(ks[6], (M, d)),
             jnp.broadcast_to(jnp.eye(d) * 400.0, (M, d, d)))
    cc = jnp.full((M,), C, jnp.int32).at[3].set(C // 2)
    check("online_update", run(online_moments_update, *state, chunk, cc),
          ref(online_moments_update_ref, *state, chunk, cc),
          [TOL_ONLINE["count"], TOL_ONLINE["mean"], TOL_ONLINE["m2"]])

    X = jax.random.normal(ks[7], (N, d))
    y = jnp.where(jax.random.uniform(ks[5], (N,)) < 0.5, 1.0, -1.0)
    beta = jax.random.normal(ks[6], (d,)) * 0.3
    check("logreg_loglik", run(logreg_loglik_grad, lane_dense(X, y), beta),
          ref(logreg_loglik_grad_ref, X, y, beta),
          [TOL_LOGREG["loglik"], TOL_LOGREG["grad"]])
    if no_kernel:
        failed.append(f"no tpu_custom_call in the compiled program of {no_kernel}")
    if failed:
        raise AssertionError("; ".join(failed))
    return {"maxabs_vs_ref": out, "machine_kde_none_maxabs_vs_f64": f64_maxabs}


def phase_mesh():
    from repro.api import Pipeline, RunSpec

    base = dict(model="logreg", M=20, T=2000, groundtruth_T=4000,
                stream_every=STREAM_EVERY, combiner=("parametric", "online"))
    boards, draws = {}, {}
    for label, shape in (("mesh", (4, 1)), ("vmap", (1, 1))):
        pipe = Pipeline(RunSpec(**base, mesh_shape=shape))
        pipe.stream_combine()
        boards[label] = pipe.score()
        draws[label] = pipe._draws.theta
    bm, bv = boards["mesh"], boards["vmap"]
    if bm.backend != "shard_map[fused](4 devices)" or bv.backend != "vmap[fused]":
        raise AssertionError(f"backends {bm.backend!r} / {bv.backend!r}")
    # None would mean the compiled-HLO collective assert never ran; its value
    # counts the collectives it found, and 0 (none at all) is the
    # collective-free outcome the assert exists to prove
    if bm.collectives_checked is None:
        raise AssertionError("the mesh programs' HLO collective assert did not run")
    devices = {s.device for s in draws["mesh"].addressable_shards}
    if len(devices) != 4:
        raise AssertionError(f"mesh draws sit on {len(devices)} devices, not 4")
    # the fused-board contract of tests/test_mesh_stream.py: same combiners,
    # finite, within 1e-2 relative (fused mesh and vmap programs are
    # different executables, so draws need not be bitwise)
    if set(bm.errors) != set(bv.errors) or not bm.errors:
        raise AssertionError(f"combiners {sorted(bm.errors)} vs {sorted(bv.errors)}")
    for name, ev in bv.errors.items():
        em = bm.errors[name]
        if not (math.isfinite(ev) and math.isfinite(em)):
            raise AssertionError(f"{name}: non-finite board {ev} / {em}")
        if abs(ev - em) > 1e-2 * max(1.0, abs(ev)):
            raise AssertionError(f"{name}: mesh {em} vs vmap {ev} beyond 1e-2")
    tm, tv = np.asarray(jax.device_get(draws["mesh"])), np.asarray(jax.device_get(draws["vmap"]))
    return {"backend": bm.backend, "collectives_checked": bm.collectives_checked,
            "errors": {"mesh": bm.errors, "vmap": bv.errors},
            "draws_bitwise_fraction": float(np.mean(tm == tv)),
            "draws_maxabs": float(np.max(np.abs(tm - tv))),
            "timings": {"mesh": bm.timings, "vmap": bv.timings}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip mesh path and its 1-device comparison")
    args = ap.parse_args(argv)
    want_devices = 4 if args.four_chips else 1

    # a. device check — before anything compiles
    devices = jax.devices()
    if devices[0].platform != "tpu":
        _stop(f"no TPU found: JAX's first device is {devices[0].platform!r}")
    if len(devices) < want_devices:
        _stop(f"needs {want_devices} TPU chips, JAX sees {len(devices)}")
    try:
        from repro.kernels import default_interpret
        from repro.utils.compile_cache import enable_compile_cache
    except ImportError as e:
        _stop(f"cannot import the repro package from {ROOT / 'src'}: {e}")
    if default_interpret():
        _stop("Pallas kernels resolve to interpret mode (REPRO_PALLAS_INTERPRET?)")
    cache_dir = enable_compile_cache()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(json.dumps({"phase": "a_device", "ok": True, "device": device,
                      "compile_cache": cache_dir}), flush=True)

    phases = ([("mesh_4chip", phase_mesh)] if args.four_chips else [
        ("b_batch", phase_batch), ("c_fused_stream", phase_stream),
        ("d_serve", phase_serve), ("e_kernels", phase_kernels),
    ])
    from repro.utils.spans import span

    all_ok = True
    for name, fn in phases:
        with span(f"smoke.{name}") as phase:
            try:
                info, ok = fn(), True
            except Exception:  # noqa: BLE001 — report the phase, run the rest
                info, ok = {"error": traceback.format_exc(limit=8)}, False
        rec = {"phase": name, "ok": ok, "wall_s": phase.seconds,
               "xla_compile_s": _compile_s(phase), **info}
        print(json.dumps(rec, default=float), flush=True)
        all_ok &= ok
    if not all_ok:
        print(json.dumps({"ok": False, "device": device}))
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
