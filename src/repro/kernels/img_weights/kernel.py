"""Pallas TPU kernel: batched IMG mixture log-weights (paper Eq. 3.5).

TPU-native layout (not a CUDA port — there is no warp/SMEM notion here):

- grid = (P // block_p, d // block_d): parallel over candidate components,
  *arbitrary* (sequential-accumulate) over feature blocks.
- Each grid step loads a (block_p, M, block_d) VMEM tile — the M axis stays
  fully resident (M ≤ 64 machines ⇒ ≤ 64·block_p·block_d·4B, sized for VMEM).
- SSE is accumulated across d-blocks in an f32 VMEM scratch (block_p, 1)
  and scaled by −1/(2h²) once on the last d-block; ``ops.py`` subtracts the
  log-normalizer. The output is the ``(P, 1)`` column of those rows (a 2-D
  block whose last dim equals the array's), and the scalar bandwidth ``h``
  is read from SMEM.
- All reductions are VPU-friendly (axis=1/2 sums over a dense tile); no
  gather/scatter — the caller materializes the (P, M, d) selection, which for
  Algorithm-1-style sweeps is a cheap take_along_axis outside the kernel.

The d-axis padding contract: padded features MUST be zero in ``theta`` (then
θ̄ is zero there too and the SSE contribution vanishes) — ``ops.py`` enforces
this. Padded P rows produce garbage and are sliced off by ``ops.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _img_weights_kernel(theta_ref, h_ref, out_ref, acc_ref, *, n_dblocks: int):
    j = pl.program_id(1)  # d-block index (sequential accumulation axis)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    t = theta_ref[...].astype(jnp.float32)  # (block_p, M, block_d)
    mean = jnp.mean(t, axis=1, keepdims=True)
    sq = jnp.sum((t - mean) ** 2, axis=2)  # (block_p, M)
    acc_ref[...] += jnp.sum(sq, axis=1, keepdims=True)  # (block_p, 1)

    @pl.when(j == n_dblocks - 1)
    def _finalize():
        h = h_ref[0]
        out_ref[...] = -acc_ref[...] * (0.5 / (h * h))


@functools.partial(jax.jit, static_argnames=("block_p", "block_d", "interpret"))
def img_log_weights_kernel(
    theta: jnp.ndarray,  # (P, M, d) — P, d already padded to block multiples
    h: jnp.ndarray,  # (1,) float32
    *,
    block_p: int = 256,
    block_d: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    P, M, d = theta.shape
    n_p, n_d = P // block_p, d // block_d
    return pl.pallas_call(
        functools.partial(_img_weights_kernel, n_dblocks=n_d),
        grid=(n_p, n_d),
        in_specs=[
            pl.BlockSpec((block_p, M, block_d), lambda i, j: (i, 0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),  # h: (1,) scalar operand
        ],
        out_specs=pl.BlockSpec((block_p, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((P, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_p, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(theta, h)[:, 0]
