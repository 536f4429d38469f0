"""Forced-multi-device SPMD integration (subprocess: 8 host devices).

The main test process keeps 1 device (dry-run owns the 512-device trick);
this test spawns one subprocess that builds a 4×2 mesh, runs a REAL
(executed, not just lowered) EP-MCMC step with the production sharding
specs, and checks chain isolation numerically.
"""

import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import functools, json
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.distributed import epmcmc
from repro.distributed.sharding import to_shardings
from repro.models.lm.config import reduced

mesh = jax.make_mesh((4, 2), ("data", "model"))
cfg = reduced(get_config("mamba2_130m"), num_layers=2, d_model=64, vocab_size=128)
C = 4
state = epmcmc.init_state(jax.random.PRNGKey(0), cfg, C)
key = jax.random.PRNGKey(1)
toks = jax.random.randint(key, (C, 2, 16), 0, cfg.vocab_size)
batch = {"tokens": toks, "labels": jnp.roll(toks, -1, -1)}

s_spec = epmcmc.state_specs(cfg, mesh, state)
b_spec = epmcmc.batch_spec(mesh, batch)
step = jax.jit(
    functools.partial(epmcmc.epmcmc_step, cfg=cfg, num_shards=C, shard_tokens=1e4, step_size=1e-4),
    in_shardings=(to_shardings(mesh, s_spec), to_shardings(mesh, b_spec)),
)
with mesh:
    state1, m1 = step(state, batch)
    # chain isolation: rerun with chain 0's tokens perturbed; only chain 0 moves
    toks2 = toks.at[0].set((toks[0] + 1) % cfg.vocab_size)
    state2, m2 = step(state, {"tokens": toks2, "labels": jnp.roll(toks2, -1, -1)})

l1 = jax.device_get(m1["loss_per_chain"]); l2 = jax.device_get(m2["loss_per_chain"])
hlo = step.lower(state, batch).compile().as_text()
n = epmcmc.assert_no_cross_chain_collectives(hlo, mesh)
print(json.dumps({
    "chain0_moved": bool(abs(l1[0] - l2[0]) > 0),
    "others_fixed": bool(all(abs(float(a) - float(b)) == 0.0 for a, b in zip(l1[1:], l2[1:]))),
    "n_collectives_checked": n,
    "devices": jax.device_count(),
}))
"""


_SAMPLER_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp
from repro.api import sample_subposteriors
from repro.models.bayes import get_model

model = get_model("poisson")
key = jax.random.PRNGKey(0)
data, _ = model.generate_data(key, 2000)
res = sample_subposteriors(
    jax.random.fold_in(key, 1), model, data, 4, 100,
    sampler="gibbs", warmup=50, burn_in=20, step_size=0.15,
)
print(json.dumps({
    "devices": jax.device_count(),
    "backend": res.backend,
    "collectives_checked": res.collectives_checked,
    "theta_shape": list(res.theta.shape),
    "finite": bool(jnp.all(jnp.isfinite(res.theta))),
    "accept_one": bool(jnp.all(res.accept == 1.0)),  # Gibbs always accepts
}))
"""


@pytest.mark.slow
def test_sampling_stage_shard_maps_with_no_cross_chain_collectives():
    """The mcmc_run sampling stage on a forced 4-device mesh: the chunked
    shard_map backend, compiled-HLO collective check passes, chains produce
    finite (M, T, d) θ."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", _SAMPLER_SCRIPT], capture_output=True, text=True,
        timeout=420, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["devices"] == 4
    assert rec["backend"] == "shard_map[chunked](4 devices)"
    assert rec["collectives_checked"] is not None  # HLO assert actually ran
    assert rec["theta_shape"] == [4, 100, 2]
    assert rec["finite"] is True
    assert rec["accept_one"] is True


@pytest.mark.slow
def test_epmcmc_step_on_8_devices_executes_and_isolates():
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        timeout=420, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["devices"] == 8
    assert rec["chain0_moved"] is True
    assert rec["others_fixed"] is True  # data of chain c only affects chain c
