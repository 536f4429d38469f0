"""Scenario-matrix driver: sweep RunSpecs with compiled-executable reuse.

``run_matrix(specs)`` executes an iterable of :class:`RunSpec` cells and
emits a tidy results table (stdout + JSON). The point, beyond the loop, is
**compile hygiene** at sweep scale:

- specs are grouped by :meth:`RunSpec.executable_signature`; one jitted
  sampling program is built per group with ``seed`` (the RNG key) and
  ``step_size`` as *runtime* arguments, so a sweep over seeds/step sizes
  lowers exactly once per distinct signature instead of once per cell;
- groundtruth chains get the same treatment keyed by
  :meth:`RunSpec.groundtruth_signature`;
- stage *outputs* are reused too: cells that differ only in combiner share
  one set of subposterior draws and one groundtruth chain.

The returned :class:`MatrixResult` carries per-cell rows plus the compile
accounting (``n_executables`` vs ``n_specs``) that
``tests/test_api.py::test_run_matrix_compiles_once_per_signature`` locks.

Two execution backends (``backend=``):

- ``"vmap"`` (default) — every cell runs on the single-device vmap path;
- ``"mesh_fanout"`` — independent *cells* fan out over mesh slices: each
  signature group stacks its pending cells along a leading axis and runs
  one ``shard_map(vmap(cell))`` program over a 1-axis device mesh, with
  the compiled HLO asserted collective-free (cells never talk to each
  other — the paper's embarrassing parallelism, one level up).

Either way, a spec carrying its *own* ``mesh_shape`` (sharding chains
within a cell) is rejected — that belongs to :class:`repro.api.Pipeline`.

CLI (the CI ``scenario-matrix`` smoke job)::

  PYTHONPATH=src python -m repro.api.matrix \\
      --models poisson,linear --samplers rwmh,gibbs \\
      --combiners parametric,nonparametric --M 4 --T 200 --json perf/
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import time
from typing import Any, Callable, Dict, Iterable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.api.spec import RunSpec
from repro.api.pipeline import (
    combine_spec_draws,
    groundtruth_step_size,
    resolve_metric,
)
from repro.api.backends import BackendId
from repro.api.hlo import assert_no_cross_chain_collectives
from repro.api.sampling import (
    _shard_axes,
    _whole_chain,
    chain_mesh,
    is_padded,
    make_shard_kernel,
)
from repro.core.subposterior import partition_data
from repro.models.bayes import get_model
from repro.utils.compile_cache import enable_compile_cache

Signature = Tuple[Any, ...]


class MatrixResult(NamedTuple):
    """Outcome of one sweep: tidy rows + compile-cache accounting."""

    rows: List[Dict[str, Any]]
    n_specs: int
    n_executables: int  # distinct sampling programs compiled
    n_groundtruth_executables: int
    signatures: Dict[str, int]  # repr(signature) -> specs served
    backend: str = "vmap"  # BackendId string of the sampling executor

    def table(self) -> str:
        head = f"{'spec_id':12s} {'model':8s} {'sampler':8s} {'combiner':16s} " \
               f"{'M':>3s} {'T':>5s} {'seed':>4s} {'acc':>5s} {'metric':6s} {'error':>10s} {'wall_s':>7s}"
        lines = [head, "-" * len(head)]
        for r in self.rows:
            lines.append(
                f"{r['spec_id']:12s} {r['model']:8s} {r['sampler']:8s} "
                f"{r['combiner']:16s} {r['M']:3d} {r['T']:5d} {r['seed']:4d} "
                f"{r['accept']:5.2f} {r['metric']:6s} {r['error']:10.4f} "
                f"{r['wall_s']:7.2f}"
            )
        lines.append(
            f"# {self.n_specs} cells on {self.backend}, "
            f"{self.n_executables} sampling executables, "
            f"{self.n_groundtruth_executables} groundtruth "
            "executables (compile-cache hits for the rest)"
        )
        return "\n".join(lines)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "rows": self.rows,
            "n_specs": self.n_specs,
            "n_executables": self.n_executables,
            "n_groundtruth_executables": self.n_groundtruth_executables,
            "signatures": self.signatures,
            "backend": self.backend,
        }


class ExecutableCache:
    """Per-signature jit cache. ``seed``/``step_size`` stay runtime inputs,
    so every spec in a group reuses one lowered program. Public: benchmarks
    (``bench_samplers``) time cells through the same cache the sweep uses."""

    def __init__(self):
        self.sample: Dict[Signature, Callable] = {}
        self.groundtruth: Dict[Signature, Callable] = {}
        self._raw: Dict[Signature, Callable] = {}

    def raw_sample_fn(self, spec: RunSpec, model, padded: bool) -> Callable:
        """The unjitted cell body ``(shards, counts, keys, step_size) ->
        (theta, accept)`` — what ``sample_fn`` jits, and what the mesh
        fan-out vmaps a second time over a leading *cell* axis."""
        sig = spec.executable_signature() + (padded,)
        if sig not in self._raw:
            sk = make_shard_kernel(
                model,
                spec.M,
                spec.resolved_sampler(),
                sgld_batch=spec.sgld_batch,
                use_counts=padded,
                sampler_options=spec.sampler_options,
            )
            T, burn, warm = spec.T, spec.resolved_burn_in(), spec.warmup

            def run(shards, counts, keys, step_size):
                one = lambda s, c, k: _whole_chain(
                    sk, s, c, k,
                    num_samples=T, burn_in=burn, warmup=warm,
                    step_size=step_size,
                )
                in_axes = (_shard_axes(shards, model.shard_keys, 0, None), 0, 0)
                return jax.vmap(one, in_axes=in_axes)(shards, counts, keys)

            self._raw[sig] = run
        return self._raw[sig]

    def sample_fn(self, spec: RunSpec, model, padded: bool) -> Callable:
        sig = spec.executable_signature() + (padded,)
        if sig not in self.sample:
            self.sample[sig] = jax.jit(self.raw_sample_fn(spec, model, padded))
        return self.sample[sig]

    def groundtruth_fn(self, spec: RunSpec, model) -> Callable:
        sig = spec.groundtruth_signature()
        if sig not in self.groundtruth:
            sk = make_shard_kernel(
                model, 1, spec.resolved_sampler(),
                sgld_batch=spec.sgld_batch, use_counts=False,
                sampler_options=spec.sampler_options,
            )
            gt_T, warm = spec.groundtruth_T, spec.warmup

            def run(data, key, step_size):
                theta, _ = _whole_chain(
                    sk, data, jnp.zeros((), jnp.int32), key,
                    num_samples=gt_T, burn_in=gt_T // 6, warmup=warm,
                    step_size=step_size,
                )
                return theta

            self.groundtruth[sig] = jax.jit(run)
        return self.groundtruth[sig]


def _partitioned(spec: RunSpec, model, key, part_cache: Dict[Tuple, Tuple]):
    """Data generation + partition, cached across cells that share them."""
    part_key = (spec.model, spec.resolved_n(), spec.seed, spec.M)
    if part_key not in part_cache:
        data, _ = model.generate_data(key, spec.resolved_n())
        shards, counts = partition_data(
            data, spec.M, only=model.shard_keys, pad=True
        )
        part_cache[part_key] = (data, shards, counts)
    return part_cache[part_key]


def _fanout_sample(
    specs: List[RunSpec],
    execs: ExecutableCache,
    part_cache: Dict[Tuple, Tuple],
    draws_cache: Dict[Tuple, Tuple],
    *,
    verbose: bool = False,
) -> int:
    """mesh_fanout prepass: fill ``draws_cache`` for every distinct draw
    cell, one ``shard_map(vmap(cell))`` program per signature group.

    Cells in a group (same executable signature, distinct seed/step) stack
    along a leading axis sharded ``P("data")`` over a 1-axis device mesh;
    the group is padded to a device multiple by repeating the last cell.
    Each compiled program's HLO is asserted collective-free — independent
    cells must stay independent on the mesh. Returns the program count.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    ndev = jax.device_count()
    if ndev < 2:
        raise ValueError(
            "run_matrix(backend='mesh_fanout') needs >=2 visible devices "
            f"but only {ndev} is — launch with e.g. "
            "XLA_FLAGS=--xla_force_host_platform_device_count=4 "
            "(or use backend='vmap')"
        )

    # group the *distinct* draw cells by signature (combiner-only sweeps
    # collapse, exactly as on the vmap path)
    groups: Dict[Signature, List[Tuple]] = {}
    pending: set = set()
    for spec in specs:
        model = get_model(spec.model)
        key = jax.random.PRNGKey(spec.seed)
        _, shards, counts = _partitioned(spec, model, key, part_cache)
        padded = is_padded(model, shards, counts, spec.resolved_sampler())
        sig = spec.executable_signature() + (padded,)
        draws_key = (sig, spec.seed, spec.step_size)
        if draws_key in draws_cache or draws_key in pending:
            continue
        pending.add(draws_key)
        keys = jax.random.split(jax.random.fold_in(key, 1), spec.M)
        groups.setdefault(sig, []).append(
            (draws_key, spec, model, padded,
             (shards, counts, keys, jnp.float32(spec.step_size)))
        )

    mesh = chain_mesh((ndev,), ("data",))
    sharding = NamedSharding(mesh, P("data"))
    n_programs = 0
    for sig, cells in groups.items():
        spec, model, padded = cells[0][1], cells[0][2], cells[0][3]
        raw = execs.raw_sample_fn(spec, model, padded)
        n_cells = len(cells)
        pad_to = -(-n_cells // ndev) * ndev
        inputs = [c[4] for c in cells] + [cells[-1][4]] * (pad_to - n_cells)
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *inputs)
        stacked = jax.tree.map(lambda x: jax.device_put(x, sharding), stacked)
        fan = jax.shard_map(
            jax.vmap(raw), mesh=mesh,
            in_specs=(P("data"),) * 4, out_specs=P("data"),
            check_vma=False,
        )
        compiled = jax.jit(fan).lower(*stacked).compile()
        assert_no_cross_chain_collectives(compiled.as_text(), mesh)
        n_programs += 1
        theta, acc = jax.block_until_ready(compiled(*stacked))
        for i, (draws_key, *_rest) in enumerate(cells):
            draws_cache[draws_key] = (theta[i], acc[i])
        if verbose:
            print(
                f"# fanout: {n_cells} cell(s) of signature "
                f"{cells[0][1].spec_id}-group over {ndev} devices "
                f"(padded to {pad_to})",
                flush=True,
            )
    return n_programs


def run_matrix(
    specs: Iterable[RunSpec],
    *,
    json_path: Optional[str] = None,
    verbose: bool = False,
    backend: str = "vmap",
) -> MatrixResult:
    """Execute every spec; compile once per signature; return tidy rows.

    RNG discipline matches :class:`repro.api.Pipeline` exactly (data from
    ``PRNGKey(seed)``, sampling ``fold_in 1``, groundtruth ``fold_in 2``,
    per-combiner streams off ``fold_in 3``), so a matrix cell and a
    standalone Pipeline over the same spec agree to the last-ulp fusion
    tolerance of tracing ``step_size`` instead of closing over it.

    ``backend="mesh_fanout"`` runs the sampling stage of independent cells
    in parallel over mesh slices (see :func:`_fanout_sample`); groundtruth
    chains and combine/score stay host-sequential either way, and the RNG
    discipline is identical, so a fanout sweep scores the same cells.
    """
    if backend not in ("vmap", "mesh_fanout"):
        raise ValueError(
            f"unknown run_matrix backend {backend!r} — expected 'vmap' or "
            "'mesh_fanout'"
        )
    specs = [s.validate() for s in specs]
    for spec in specs:
        if spec.mesh_shape is not None:
            # Pipeline owns within-cell meshes; a sweep must not quietly
            # drop the shard_map/HLO-assert request (mesh_fanout shards
            # whole cells, never the chains inside one)
            raise ValueError(
                f"spec {spec.spec_id}: run_matrix drives the vmap backend "
                f"only within a cell — mesh_shape={spec.mesh_shape} belongs "
                "to repro.api.Pipeline"
            )
    execs = ExecutableCache()
    draws_cache: Dict[Tuple, Tuple] = {}  # (sig, seed, step) -> (theta, acc)
    gt_cache: Dict[Tuple, jnp.ndarray] = {}
    part_cache: Dict[Tuple, Tuple] = {}  # (model, n, seed, M) -> stage inputs
    rows: List[Dict[str, Any]] = []
    signatures: Dict[str, int] = {}

    n_fanout = 0
    if backend == "mesh_fanout":
        # batch-sample every distinct draw cell up front; the per-spec loop
        # below then cache-hits on draws and only runs gt + combine + score
        n_fanout = _fanout_sample(
            specs, execs, part_cache, draws_cache, verbose=verbose
        )

    for spec in specs:
        t0 = time.time()
        model = get_model(spec.model)
        key = jax.random.PRNGKey(spec.seed)
        # data generation + partition reused across cells differing only in
        # sampler/combiner/step — cache-hit cells' wall_s stays honest
        data, shards, counts = _partitioned(spec, model, key, part_cache)
        padded = is_padded(model, shards, counts, spec.resolved_sampler())
        sig = spec.executable_signature() + (padded,)
        signatures[repr(sig)] = signatures.get(repr(sig), 0) + 1

        draws_key = (sig, spec.seed, spec.step_size)
        if draws_key not in draws_cache:
            fn = execs.sample_fn(spec, model, padded)
            keys = jax.random.split(jax.random.fold_in(key, 1), spec.M)
            draws_cache[draws_key] = jax.block_until_ready(
                fn(shards, counts, keys, jnp.float32(spec.step_size))
            )
        theta, acc = draws_cache[draws_key]

        # keyed on the COMPENSATED step (it depends on M, which the gt
        # signature excludes) — specs differing only in M must not share
        # a groundtruth chain run at the wrong ε
        gt_step = groundtruth_step_size(spec)
        gt_key = (spec.groundtruth_signature(), spec.seed, gt_step)
        if gt_key not in gt_cache:
            fn = execs.groundtruth_fn(spec, model)
            gt_cache[gt_key] = jax.block_until_ready(
                fn(data, jax.random.fold_in(key, 2), jnp.float32(gt_step))
            )
        gt = gt_cache[gt_key]

        # -- combine + score (eager; RNG/options shared with Pipeline) ------
        dist, label = resolve_metric(spec, model.d)
        t_row = time.time()
        for name in spec.combiner_names():
            out = combine_spec_draws(spec, key, theta, names=(name,))[name]
            err = float(dist(gt, out.samples))  # forces the async dispatch
            now = time.time()
            rows.append({
                "spec_id": spec.spec_id,
                "model": spec.model,
                "sampler": spec.resolved_sampler(),
                "combiner": name,
                "M": spec.M,
                "T": spec.T,
                "seed": spec.seed,
                "accept": float(jnp.mean(acc)),
                "metric": label,
                "error": err,
                # per-row delta (first row absorbs the cell's sampling/
                # groundtruth cost) — cumulative stamps would skew the
                # perf-trajectory JSON by row order
                "wall_s": now - t_row,
            })
            t_row = now
        if verbose:
            print(f"# cell {spec.spec_id} ({spec.model}/{spec.resolved_sampler()}) "
                  f"done in {time.time() - t0:.1f}s", flush=True)

    backend_id = (
        BackendId.mesh_fanout(jax.device_count())
        if backend == "mesh_fanout"
        else BackendId.vmap()
    )
    result = MatrixResult(
        rows=rows,
        n_specs=len(specs),
        n_executables=len(execs.sample) + n_fanout,
        n_groundtruth_executables=len(execs.groundtruth),
        signatures=signatures,
        backend=backend_id,
    )
    if json_path is not None:
        path = _json_path(json_path)
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(result.to_dict(), f, indent=1)
    return result


def _json_path(arg: str) -> str:
    """A ``.json`` arg is a file; anything else a directory getting an
    auto-named ``MATRIX_<timestamp>.json`` (mirrors ``benchmarks.run``)."""
    if arg.endswith(".json") and not os.path.isdir(arg):
        return arg
    return os.path.join(arg, f"MATRIX_{time.strftime('%Y%m%d_%H%M%S')}.json")


def main(argv=None) -> MatrixResult:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--models", default="poisson,linear")
    ap.add_argument("--samplers", default="rwmh,gibbs")
    ap.add_argument("--combiners", default="parametric,nonparametric")
    ap.add_argument("--seeds", default="0")
    ap.add_argument("--M", type=int, default=4)
    ap.add_argument("--T", type=int, default=200)
    ap.add_argument("--warmup", type=int, default=50)
    ap.add_argument("--step", type=float, default=0.1)
    ap.add_argument("--n", type=int, default=0, help="dataset size (0 = model default)")
    ap.add_argument("--gt-T", type=int, default=400)
    ap.add_argument(
        "--metric", default="auto", choices=("auto", "l2", "logl2"),
        help="scoreboard distance (logl2 keeps narrow posteriors finite)",
    )
    ap.add_argument("--json", default=None, metavar="PATH")
    ap.add_argument(
        "--backend", default="vmap", choices=("vmap", "mesh_fanout"),
        help="mesh_fanout shards independent cells over visible devices",
    )
    args = ap.parse_args(argv)
    enable_compile_cache()

    split = lambda s: tuple(x for x in s.split(",") if x)
    specs = [
        RunSpec(
            model=m, sampler=s, combiner=c, M=args.M, T=args.T,
            warmup=args.warmup, step_size=args.step, n=args.n,
            seed=int(seed), groundtruth_T=args.gt_T,
            score_metric=args.metric,
        )
        for m, s, c, seed in itertools.product(
            split(args.models), split(args.samplers),
            split(args.combiners), split(args.seeds),
        )
    ]
    result = run_matrix(
        specs, json_path=args.json, verbose=True, backend=args.backend
    )
    print(result.table())
    return result


if __name__ == "__main__":
    main()
