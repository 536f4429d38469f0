"""EP-MCMC driver CLI — a thin argparse adapter over :mod:`repro.api`.

Every flag maps onto a field of :class:`repro.api.RunSpec`; execution is one
:class:`repro.api.Pipeline` run (partition → sample → combine → score, same
RNG discipline and scoreboard as ever — fixed seeds reproduce pre-``repro.api``
numbers bitwise). Models, samplers, and combiners are resolved by registry
name; adding an entry to any registry makes it reachable here with zero
driver changes.

  PYTHONPATH=src python -m repro.launch.mcmc_run --model logreg --M 10 \
      --sampler hmc --samples 2000
  PYTHONPATH=src python -m repro.launch.mcmc_run --model poisson --sampler gibbs
  PYTHONPATH=src python -m repro.launch.mcmc_run --model gmm --M 10

Step sizes are adapted per chain by the dual-averaging warmup phase
(``--warmup``, sampler-specific acceptance targets) — there are no hand-tuned
per-model step constants.

The sampling stage runs vmapped on one device, or — given >1 device (e.g.
``XLA_FLAGS=--xla_force_host_platform_device_count=4``) — ``shard_map``-ped
over the ``data`` axis of a mesh, one chain group per device
(``--mesh-shape``, or automatic when the device count divides ``--M``).
Either way the stage contains zero cross-chain collectives; on the mesh
path this is *asserted on the compiled HLO* via
:func:`repro.distributed.epmcmc.assert_no_cross_chain_collectives` — the
paper's "embarrassingly parallel" claim, machine-checked per run. Since the
:mod:`repro.api.backends` unification the mesh composes with
``--stream-every`` and ``--checkpoint-dir``: chunk programs run on the mesh
and every chunk program's HLO is asserted the same way.

``--serve`` runs the same Pipeline behind the :mod:`repro.serve` posterior
server: sampling streams chunks into the folder task while concurrent
readers (``--serve-readers`` self-probes, plus any external
``repro.serve.ServeClient``) query mean/cov, quantiles, predictive draws,
and machine-KDE log density with staleness metadata on every response.

The sampling engine itself lives in :mod:`repro.api.sampling`; the historical
module-level names (``make_shard_sampler``, ``sample_subposteriors``,
``groundtruth_chain``, ``SampleResult``) are re-exported here with a
``DeprecationWarning`` — import them from ``repro.api`` instead.
"""

from __future__ import annotations

import argparse
import warnings

from repro.api import Pipeline, RunSpec
from repro.core.combiners import available_combiners
from repro.models.bayes import available_models
from repro.samplers import available_samplers
from repro.utils.compile_cache import enable_compile_cache

# historical internals, now owned by repro.api.sampling — resolved lazily so
# importing this CLI module stays cheap and old imports keep working (warned)
_MOVED = (
    "SampleResult",
    "make_shard_sampler",
    "sample_subposteriors",
    "groundtruth_chain",
    "_shard_axes",
    "_sample_on_mesh",
    "LOG_L2_DIM",
)


def __getattr__(name: str):
    if name in _MOVED:
        warnings.warn(
            f"repro.launch.mcmc_run.{name} moved to repro.api — import it "
            "from repro.api (or drive whole runs via RunSpec/Pipeline)",
            DeprecationWarning,
            stacklevel=2,
        )
        if name == "LOG_L2_DIM":
            from repro.api.pipeline import LOG_L2_DIM

            return LOG_L2_DIM
        from repro.api import sampling

        return getattr(sampling, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def _parse_mesh(arg):
    """``"4,1"`` → ``(4, 1)``; ``""``/None → None (vmap or auto-mesh)."""
    if not arg:
        return None
    parts = tuple(int(x) for x in arg.split(","))
    if len(parts) == 1:
        parts = parts + (1,)
    return parts


def build_spec(args: argparse.Namespace) -> RunSpec:
    """The whole adapter: argparse namespace → declarative RunSpec."""
    return RunSpec(
        mesh_shape=_parse_mesh(getattr(args, "mesh_shape", None)),
        model=args.model,
        sampler=args.sampler,
        combiner=args.combiner,
        M=args.M,
        T=args.samples,
        warmup=args.warmup,
        burn_in=args.burn_in,
        step_size=args.step,
        sgld_batch=args.sgld_batch,
        n=args.n,
        seed=args.seed,
        groundtruth_T=args.groundtruth_samples,
        stream_every=args.stream_every,
        combiner_options={"n_batch": args.img_batch},
    )


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="logreg", choices=available_models())
    ap.add_argument("--M", type=int, default=10)
    ap.add_argument("--samples", type=int, default=2000)
    ap.add_argument("--burn-in", type=int, default=0, help="0 = paper's T/6 rule")
    ap.add_argument(
        "--sampler", default=None, choices=available_samplers(),
        help="sampler registry name (default: the model's default_sampler)",
    )
    ap.add_argument(
        "--warmup", type=int, default=200,
        help="dual-averaging step-size adaptation steps per chain",
    )
    ap.add_argument(
        "--step", type=float, default=0.1,
        help="initial step size (adapted away by warmup for MH-style kernels; "
        "the fixed step for gibbs/sgld)",
    )
    ap.add_argument(
        "--sgld-batch", type=int, default=256,
        help="SGLD minibatch size (0 = full shard)",
    )
    ap.add_argument("--n", type=int, default=0, help="dataset size (0 = paper's)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--groundtruth-samples", type=int, default=4000)
    ap.add_argument(
        "--combiner", default="all", choices=("all",) + available_combiners(),
        help="combination strategy to score (default: every registered combiner)",
    )
    ap.add_argument(
        "--img-batch", type=int, default=1,
        help="independent vmapped IMG index-chains (n_batch) for the exact combiners",
    )
    ap.add_argument(
        "--checkpoint-dir", default=None,
        help="persist/resume the sampling stage here (chunked kernel state)",
    )
    ap.add_argument(
        "--checkpoint-every", type=int, default=0,
        help="draws per sampling checkpoint (with --checkpoint-dir; 0 = at end)",
    )
    ap.add_argument(
        "--stream-every", type=int, default=0,
        help="combine-while-sampling: fold every N landed draws into the "
        "streaming combiners and print the scoreboard trajectory (0 = off)",
    )
    ap.add_argument(
        "--mesh-shape", default=None, metavar="NDATA[,NMODEL]",
        help="shard chains over a device mesh (e.g. 4,1 with "
        "XLA_FLAGS=--xla_force_host_platform_device_count=4); composes "
        "with --stream-every and --checkpoint-dir via the mesh chunk "
        "backend (default: auto-mesh when >1 device divides M)",
    )
    ap.add_argument(
        "--serve", action="store_true",
        help="posterior-as-a-service: run sampling behind a repro.serve "
        "asyncio server (needs --stream-every) and answer posterior "
        "queries while the chains extend; composes with --checkpoint-dir "
        "(restart resumes from the last checkpoint)",
    )
    ap.add_argument(
        "--serve-port", type=int, default=0,
        help="TCP port for --serve (0 = ephemeral, printed at startup)",
    )
    ap.add_argument(
        "--serve-readers", type=int, default=4,
        help="concurrent self-probe readers cycling posterior queries "
        "during --serve (each asserts staleness counters monotone — the "
        "CI smoke contract); 0 = serve without probing",
    )
    args = ap.parse_args(argv)
    enable_compile_cache()

    pipe = Pipeline(
        build_spec(args),
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
    )
    if args.serve:
        if args.stream_every <= 0:
            ap.error("--serve needs --stream-every > 0 (the serving cadence)")
        from repro.serve import serve_pipeline

        serve_pipeline(
            pipe, port=args.serve_port, probe_readers=args.serve_readers
        )
        # sampling is complete (and cached on the Pipeline): fall through to
        # the ordinary combine+score scoreboard over the served draws
    elif args.stream_every > 0:
        sr = pipe.stream_combine()
        first = sr.trajectory[0] if sr.trajectory else None
        if first is not None:
            print(
                f"streaming: first {sr.metric} estimate "
                f"({first['combiner']}, t={first['t']}) after "
                f"{first['elapsed_s']:.1f}s; "
                f"{len(sr.trajectory)} trajectory points over "
                f"{sr.t_done}/{sr.total} draws"
            )
        for row in sr.trajectory:
            err = "  -  " if row["error"] is None else f"{row['error']:.4f}"
            print(f"  t={row['t']:6d} {sr.metric}({row['combiner']:15s}) = {err}"
                  f"  [{row['elapsed_s']:.1f}s]")
    board = pipe.run()

    checked = (
        "" if board.collectives_checked is None
        else f" hlo_collectives_checked={board.collectives_checked}"
    )
    print(
        f"model={board.model} M={board.M} T={board.T} sampler={board.sampler} "
        f"warmup={args.warmup} acc={board.accept:.2f} "
        f"backend={board.backend}{checked}"
    )
    t = board.timings
    print(f"timing: {t.get('sample_s', 0.0):.1f}s parallel sampling, "
          f"{t.get('groundtruth_s', 0.0):.1f}s full chain, "
          f"{t.get('combine_s', 0.0):.1f}s all combinations")
    for k_, v in sorted(board.errors.items(), key=lambda kv: kv[1]):
        print(f"  {board.metric}({k_:15s}) = {v:.4f}")
    return dict(board.errors)


if __name__ == "__main__":
    main()
