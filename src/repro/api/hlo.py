"""HLO assertions: the "embarrassingly parallel" proof.

A chain program compiled for a device mesh must hold no collective whose
device group spans more than one chain group. :func:`collective_groups`
reads every collective and its device groups out of a compiled program's
text; :func:`assert_no_cross_chain_collectives` fails on any group that
crosses chain groups. The mesh chunk backend (:mod:`repro.api.backends`),
the matrix fan-out (:mod:`repro.api.matrix`) and the LM-scale EP-MCMC step
(:mod:`repro.distributed.epmcmc`) assert their programs with it.
"""

from __future__ import annotations

import re
from typing import Tuple

from jax.sharding import Mesh

_REPLICA_GROUPS_RE = re.compile(r"replica_groups=\{(\{[^=]*?\})\}")
# NB: the output type may be a (multi-line-wide) tuple, so match the op-kind
# token directly rather than anchoring on '= <type>'.
_COLLECTIVE_LINE_RE = re.compile(
    r"\s(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_PAIRS_RE = re.compile(r"source_target_pairs=\{([^}]*)\}")


_IOTA_GROUPS_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?"
)


def _iota_groups(ng: int, gs: int, dims, perm) -> list:
    """Decode the iota-v2 replica_groups format: [NG,GS]<=[dims]T(perm)."""
    import numpy as np

    total = 1
    for d in dims:
        total *= d
    ids = np.arange(total).reshape(dims)
    if perm is not None:
        ids = ids.transpose(perm)
    return ids.reshape(ng, gs).tolist()


def collective_groups(hlo_text: str) -> list:
    """Extract (kind, groups) for every collective in the HLO.

    Handles the explicit ``{{0,1},{2,3}}`` form, the iota-v2 form
    ``[NG,GS]<=[dims]T(perm)`` and collective-permute source/target pairs."""
    out = []
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_LINE_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        groups = []
        im = _IOTA_GROUPS_RE.search(line)
        if im:
            dims = [int(x) for x in im.group(3).split(",")]
            perm = [int(x) for x in im.group(4).split(",")] if im.group(4) else None
            groups = _iota_groups(int(im.group(1)), int(im.group(2)), dims, perm)
        else:
            gm = _REPLICA_GROUPS_RE.search(line)
            if gm:
                for grp in re.findall(r"\{([0-9,\s]*)\}", gm.group(1)):
                    ids = [int(x) for x in grp.replace(" ", "").split(",") if x]
                    if ids:
                        groups.append(ids)
        pm = _PAIRS_RE.search(line)
        if pm:
            pairs = [
                tuple(int(x) for x in p.split(","))
                for p in re.findall(r"\{([0-9,\s]*)\}", pm.group(1))
            ]
            groups = [list(p) for p in pairs]
        out.append((kind, groups))
    return out


def assert_no_cross_chain_collectives(
    hlo_text: str, mesh: Mesh, *, allow_kinds: Tuple[str, ...] = ()
) -> int:
    """Fail if any collective's device group spans >1 (pod, data) coordinate.

    Device ids on our mesh are row-major over (pod?, data, model), so the
    chain coordinate of device i is ``i // model_size``. Returns the number
    of collectives checked (all confined to the model axis). Meshes without
    a ``model`` axis (e.g. the ``run_matrix`` cell-fanout mesh, where the
    data axis indexes whole cells) treat every device as its own chain
    group — any cross-device collective fails."""
    model = dict(mesh.shape).get("model", 1)
    checked = 0
    for kind, groups in collective_groups(hlo_text):
        if kind in allow_kinds:
            continue
        for grp in groups:
            chains = {dev // model for dev in grp}
            if len(chains) > 1:
                raise AssertionError(
                    f"{kind} crosses chain groups {sorted(chains)[:4]}…: {grp[:8]}"
                )
        checked += 1
    return checked
