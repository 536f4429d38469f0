"""Every Pallas kernel of the main path compiles for a TPU v5e, with no chip.

The TPU compiler is installed with jaxlib, and it compiles for a chip that is
described rather than attached (``jax.experimental.topologies``). That finds
what interpret mode cannot: blocks that break the (8, 128) tiling rule,
layouts Mosaic and XLA disagree on, scalars in the wrong memory space. Each
case lowers the kernel's public ``ops.py`` wrapper with ``interpret=False``
at the paper's logreg widths (d=50, M=10 machines, T=Q=2000) and asserts the
compiled program holds the Mosaic kernel (``tpu_custom_call``), not a
fallback.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and each test worker imports
every test file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.img_weights import img_log_weights
from repro.kernels.kde_density import kde_log_density, machine_kde_log_density
from repro.kernels.logreg_loglik import logreg_loglik_grad
from repro.kernels.online_update import online_moments_update

D, M, T, Q = 50, 10, 2000, 2000
CHUNK = 200  # the streaming cadence of the fused `online` fold
N_SHARD = 5000  # 50,000 rows over M=10 machines


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _cases():
    f32, i32 = jnp.float32, jnp.int32
    cases = {
        f"machine_kde_{r}": (
            functools.partial(
                machine_kde_log_density, reduce=r, impl="kernel", interpret=False
            ),
            [((Q, D), f32), ((M, T, D), f32), ((M,), f32), ((M,), i32)],
        )
        for r in ("none", "product", "mixture", "product_mixture")
    }
    cases["online_update"] = (
        functools.partial(online_moments_update, interpret=False),
        [((M,), f32), ((M, D), f32), ((M, D, D), f32), ((M, CHUNK, D), f32),
         ((M,), i32)],
    )
    cases["img_weights"] = (
        functools.partial(img_log_weights, interpret=False),
        [((T, M, D), f32), ((), f32)],
    )
    cases["kde_log_density"] = (
        functools.partial(kde_log_density, interpret=False),
        [((Q, D), f32), ((T, D), f32), ((), f32)],
    )
    cases["logreg_loglik"] = (
        functools.partial(logreg_loglik_grad, interpret=False),
        [((D, N_SHARD), f32), ((D,), f32)],
    )
    # covtype: 48 chains over 12,105-row shards of d=54, and the full-data
    # chain's 581,012 rows, which take many blocks
    cases["logreg_loglik_chains"] = (
        jax.vmap(functools.partial(logreg_loglik_grad, interpret=False)),
        [((48, 54, 12105), f32), ((48, 54), f32)],
    )
    cases["logreg_loglik_full_data"] = (
        functools.partial(logreg_loglik_grad, interpret=False),
        [((54, 581012), f32), ((54,), f32)],
    )
    return cases


CASES = _cases()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
