"""Multi-controller launch path: ``python -m repro.api.launch``.

The paper's algorithm is embarrassingly parallel *across machines*, not
just across devices — each machine owns a data shard, runs its subposterior
chains with zero communication, and only the combination step talks. This
module is that deployment shape as a CLI: every process (one per host/rank)
runs the same command with its ``--process-id``, and

- **data** is generated identically everywhere from the spec seed (rank
  *slices* are taken from the same global partition, so the union of ranks
  is exactly the single-host run);
- **sampling** drives the rank's chain slice through
  :func:`repro.api.backends.get_chunk_backend` chunk programs of width 1,
  one chain at a time — per-chain RNG keys are the rank's slice of the
  *global* ``split(fold_in(key, 1), M)``, and because every chain runs the
  same width-1 executable whatever the rank count, a launch is
  **rank-count-invariant**: 1, 2, or M processes produce bitwise-identical
  draws per chain (a width-M vmap would fuse differently at the ulp level
  and diverge under rejection loops);
- **combination** folds each chunk into a moments-backed streaming
  combiner state (``repro.core.combiners.get_streaming_combiner``), and
  only that O(M·d²) state ever crosses hosts: ranks exchange their slices
  through the ``jax.distributed`` coordinator's key-value store and
  concatenate along the chain axis (per-chain Welford states are disjoint,
  so the concatenation is bitwise the single-host state). The draws
  themselves — the O(M·T·d) payload — never leave their host.

The KV-store exchange is deliberately platform-neutral: CPU hosts cannot
run multi-process XLA collectives at all ("Multiprocess computations
aren't implemented on the CPU backend"), and the state is small enough
that a device collective would buy nothing. That is also why only
moments-backed combiners (``--combiner online``) are launchable —
draw-buffer streaming states grow with T, and shipping them cross-host
would be the gather this path exists to avoid.

2-process smoke (two shells, or ``tests/test_launch_distributed.py``)::

  python -m repro.api.launch --coordinator localhost:9123 \\
      --num-processes 2 --process-id 0 --model poisson --sampler gibbs \\
      --M 4 --T 200 --json out0.json &
  python -m repro.api.launch --coordinator localhost:9123 \\
      --num-processes 2 --process-id 1 --model poisson --sampler gibbs \\
      --M 4 --T 200

Rank 0 writes/prints the finalized result; with ``--num-processes 1`` (the
default) no coordinator is needed and the same code path runs locally —
the reference a distributed run must reproduce.
"""

from __future__ import annotations

import argparse
import base64
import io
import json
import time
import zlib
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

# NOTE: repro imports are deliberately lazy (inside the functions below) —
# several modules build jnp constants at import time, and JAX refuses
# jax.distributed.initialize() after any computation has run. main() must
# initialize first, import second.

PyTree = Any

# moments-backed streaming combiners: state size independent of T, hence
# cheap to exchange cross-host. Anything else would ship draw buffers.
LAUNCHABLE_COMBINERS = ("online",)


def _kv_allgather(tag: str, tree: PyTree, rank: int, num_processes: int,
                  *, timeout_ms: int = 120_000) -> PyTree:
    """Allgather a small pytree across ranks via the coordinator KV store,
    concatenating every leaf along its leading (chain) axis in rank order."""
    from jax._src import distributed  # the coordinator client lives here

    client = distributed.global_state.client
    leaves, treedef = jax.tree.flatten(tree)
    buf = io.BytesIO()
    # fixed-width names keep np.load's file order stable past 10 leaves
    np.savez(buf, **{
        f"a{i:03d}": np.asarray(jax.device_get(leaf))
        for i, leaf in enumerate(leaves)
    })
    client.key_value_set(
        f"{tag}/{rank}", base64.b64encode(buf.getvalue()).decode("ascii")
    )
    client.wait_at_barrier(f"{tag}/barrier", timeout_ms)
    per_rank = []
    for r in range(num_processes):
        raw = base64.b64decode(client.blocking_key_value_get(
            f"{tag}/{r}", timeout_ms
        ))
        with np.load(io.BytesIO(raw)) as z:
            per_rank.append([z[f"a{i:03d}"] for i in range(len(leaves))])
    merged = [
        np.concatenate([g[i] for g in per_rank], axis=0)
        for i in range(len(leaves))
    ]
    return jax.tree.unflatten(treedef, [jnp.asarray(m) for m in merged])


def _slice_chains(model, shards, counts, keys, lo: int, hi: int):
    """This rank's chain slice of the global partition: per-datum shard
    leaves and per-chain arrays sliced, broadcast leaves kept whole."""
    from repro.api.sampling import _shard_axes

    axes = _shard_axes(shards, model.shard_keys, 0, None)
    local_shards = jax.tree.map(
        lambda x, a: x[lo:hi] if a == 0 else x, shards, axes
    )
    return local_shards, counts[lo:hi], keys[lo:hi]


def run_launch(spec, *, num_processes: int = 1,
               process_id: int = 0) -> Dict[str, Any]:
    """One rank of the multi-controller run; returns the result record
    (every rank computes the identical finalized estimate)."""
    from repro.api.backends import BackendId, get_chunk_backend
    from repro.api.sampling import is_padded
    from repro.core.combiners import filter_options, get_streaming_combiner
    from repro.core.subposterior import partition_data
    from repro.models.bayes import get_model

    spec = spec.validate()
    names = spec.combiner_names()
    bad = [n for n in names if n not in LAUNCHABLE_COMBINERS]
    if bad:
        raise ValueError(
            f"combiner(s) {bad} cannot run on the launch path — only the "
            f"moments-backed {LAUNCHABLE_COMBINERS} exchange O(M*d^2) state "
            "across hosts (draw-buffer streaming states grow with T; run "
            "those single-host via Pipeline.stream_combine)"
        )
    if spec.M % num_processes != 0:
        raise ValueError(
            f"M={spec.M} chains must divide evenly over "
            f"--num-processes {num_processes}"
        )
    if spec.mesh_shape is not None:
        raise ValueError(
            "the launch path shards chains across *processes* — "
            f"mesh_shape={spec.mesh_shape} (within-process device mesh) "
            "belongs to repro.api.Pipeline"
        )

    t_start = time.time()
    model = get_model(spec.model)
    key = jax.random.PRNGKey(spec.seed)
    data, _ = model.generate_data(key, spec.resolved_n())
    shards, counts = partition_data(
        data, spec.M, only=model.shard_keys, pad=True
    )
    padded = is_padded(model, shards, counts, spec.resolved_sampler())
    keys_all = jax.random.split(jax.random.fold_in(key, 1), spec.M)

    chains_per_rank = spec.M // num_processes
    lo, hi = process_id * chains_per_rank, (process_id + 1) * chains_per_rank
    local_shards, local_counts, local_keys = _slice_chains(
        model, shards, counts, keys_all, lo, hi
    )

    # Every chain runs through the SAME width-1 chunk programs, whatever the
    # rank count: a vmap over 2 chains and a vmap over 4 fuse differently at
    # the ulp level, and samplers with rejection loops (gibbs' gamma draws,
    # MH accepts) amplify one flipped comparison into a divergent chain.
    # Width-1 execution makes the run *rank-count-invariant* — launching on
    # 1, 2, or M hosts produces bitwise-identical draws per chain — at the
    # cost of the vmap batching a single-host Pipeline would enjoy.
    backend = get_chunk_backend(
        model,
        1,
        spec.resolved_sampler(),
        warmup=spec.warmup,
        burn_in=spec.resolved_burn_in(),
        step_size=spec.step_size,
        sgld_batch=spec.sgld_batch,
        sampler_options=spec.sampler_options,
        use_counts=padded,
        shards=local_shards,
    )

    def chain_slice(c):
        sh, cn, ks = _slice_chains(
            model, local_shards, local_counts, local_keys, c, c + 1
        )
        return backend.prepare(sh, cn, ks)

    T = spec.T
    cadence = spec.stream_every if spec.stream_every > 0 else T
    chains = [chain_slice(c) for c in range(chains_per_rank)]
    carries = []
    for sh, cn, ks in chains:
        state, eps, k_collect = backend.setup(sh, cn, ks)
        ck = jax.vmap(lambda k: jax.random.split(k, T))(k_collect)
        carries.append({"state": state, "eps": eps, "ck": ck})

    scs = {name: get_streaming_combiner(name) for name in names}
    options = dict(
        {"rescale": True, "n_batch": 1}, **dict(spec.combiner_options)
    )
    states: Dict[str, Any] = {name: None for name in names}
    accept_sum = jnp.zeros((chains_per_rank,), jnp.float32)
    for t0 in range(0, T, cadence):
        t1 = min(t0 + cadence, T)
        thetas, accs = [], []
        for (sh, cn, _), carry in zip(chains, carries):
            carry["state"], theta_c, acc_c = backend.next_chunk(
                sh, cn, carry["eps"], carry["state"], carry["ck"][:, t0:t1]
            )
            thetas.append(theta_c)
            accs.append(acc_c)
        theta = jnp.concatenate(thetas, axis=0)
        accept_sum = accept_sum + jnp.concatenate(accs, axis=0)
        for name in names:
            sc = scs[name]
            if states[name] is None:
                states[name] = sc.init(chains_per_rank, model.d)
            states[name] = sc.update(states[name], theta)

    # -- the only cross-host traffic: combine state + accept counts -------
    if num_processes > 1:
        for name in names:
            states[name] = _kv_allgather(
                f"combine/{name}", states[name], process_id, num_processes
            )
        accept_sum = _kv_allgather(
            "accept", accept_sum, process_id, num_processes
        )

    # finalize with Pipeline's exact RNG discipline — the distributed run
    # must score as the same experiment
    kc = jax.random.fold_in(key, 3)
    combined: Dict[str, Any] = {}
    for name in names:
        k_name = jax.random.fold_in(kc, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        fn = scs[name].finalize
        res = fn(k_name, states[name], T, **filter_options(fn, options))
        combined[name] = np.asarray(jax.device_get(res.samples))

    record = {
        "spec_id": spec.spec_id,
        "backend": BackendId.distributed(num_processes),
        "model": spec.model,
        "sampler": spec.resolved_sampler(),
        "M": spec.M,
        "T": T,
        "seed": spec.seed,
        "num_processes": num_processes,
        "process_id": process_id,
        "accept": float(jnp.mean(accept_sum) / T),
        "combined": {
            name: {
                "mean": np.mean(s, axis=0).tolist(),
                "std": np.std(s, axis=0).tolist(),
                "samples": s.tolist(),
            }
            for name, s in combined.items()
        },
        "wall_s": time.time() - t_start,
    }
    return record


def main(argv=None) -> Optional[Dict[str, Any]]:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                    help="jax.distributed coordinator (rank 0's address); "
                    "required when --num-processes > 1")
    ap.add_argument("--num-processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    ap.add_argument("--model", default="poisson")
    ap.add_argument("--sampler", default=None)
    ap.add_argument("--combiner", default="online")
    ap.add_argument("--M", type=int, default=4)
    ap.add_argument("--T", type=int, default=200)
    ap.add_argument("--warmup", type=int, default=50)
    ap.add_argument("--step", type=float, default=0.1)
    ap.add_argument("--n", type=int, default=0,
                    help="dataset size (0 = model default)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--stream-every", type=int, default=0,
                    help="chunk cadence (0 = one chunk)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="rank 0 writes the result record here")
    args = ap.parse_args(argv)

    if args.num_processes > 1:
        if args.coordinator is None:
            raise SystemExit(
                "--num-processes > 1 needs --coordinator HOST:PORT "
                "(rank 0's address, same value on every rank)"
            )
        jax.distributed.initialize(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )

    from repro.api.spec import RunSpec  # after initialize — see note above
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    spec = RunSpec(
        model=args.model, sampler=args.sampler, combiner=args.combiner,
        M=args.M, T=args.T, warmup=args.warmup, step_size=args.step,
        n=args.n, seed=args.seed, stream_every=args.stream_every,
    )
    record = run_launch(
        spec, num_processes=args.num_processes, process_id=args.process_id
    )
    if args.process_id == 0:
        out = json.dumps(record, indent=1)
        if args.json:
            with open(args.json, "w") as f:
                f.write(out + "\n")
        print(out)
    if args.num_processes > 1:
        jax.distributed.shutdown()
    return record if args.process_id == 0 else None


if __name__ == "__main__":
    main()
