"""Pallas TPU kernel: fused logistic log-likelihood + gradient.

TPU-native design (vs the CPU/Stan loop the paper ran):

- grid = (N // block_n,): one sequential pass over row blocks. Each step
  pulls a (block_n, d) tile of X into VMEM, does the matvec on the MXU
  (block_n × d @ d × 1), the log-sigmoid on the VPU, and accumulates BOTH
  the scalar ℓ and the d-vector ∇ℓ in f32 VMEM scratch — X is read ONCE
  from HBM for value+grad (arithmetic intensity 2× the naive two-pass).
- d stays resident (d ≤ ~8k fits VMEM alongside the row tile; the paper's
  experiments are d ≤ 54 — sampling-regime posteriors are low-dim).
- ``w`` is a {0,1} row mask so ops.py can pad N without biasing ℓ: a padded
  row would otherwise add log σ(0) = −log 2.

The matvec-as-matmul shape (block_n, d)·(d, 1) keeps the MXU utilized when
callers batch multiple chains: beta may be (d, C) for C parallel chains
(vmapped subposterior chains on one device), giving a true matmul.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _logreg_kernel(x_ref, y_ref, w_ref, beta_ref, loglik_ref, grad_ref, acc_l, acc_g, *, n_blocks: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_l[...] = jnp.zeros_like(acc_l)
        acc_g[...] = jnp.zeros_like(acc_g)

    x = x_ref[...].astype(jnp.float32)  # (block_n, d)
    y = y_ref[...].astype(jnp.float32)  # (block_n, C)
    w = w_ref[...].astype(jnp.float32)  # (block_n, 1)
    beta = beta_ref[...].astype(jnp.float32)  # (d, C)

    z = y * jax.lax.dot(x, beta, precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)  # (block_n, C)
    # log σ(z) = −softplus(−z), computed stably on the VPU
    loglik = -jnp.sum(w * jnp.logaddexp(0.0, -z), axis=0)  # (C,)
    coeff = w * y * jax.nn.sigmoid(-z)  # (block_n, C)
    grad = jax.lax.dot(x.T, coeff, precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)  # (d, C)

    acc_l[...] += loglik
    acc_g[...] += grad

    @pl.when(i == n_blocks - 1)
    def _finalize():
        loglik_ref[...] = acc_l[...]
        grad_ref[...] = acc_g[...]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def logreg_loglik_grad_kernel(
    X: jnp.ndarray,  # (N, d) padded: N % block_n == 0
    y: jnp.ndarray,  # (N, C)
    w: jnp.ndarray,  # (N, 1) row mask
    beta: jnp.ndarray,  # (d, C)
    *,
    block_n: int = 1024,
    interpret: bool = False,
):
    N, d = X.shape
    C = beta.shape[1]
    n_blocks = N // block_n
    kernel = functools.partial(_logreg_kernel, n_blocks=n_blocks)
    return pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((block_n, C), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((d, C), lambda i: (0, 0)),  # beta resident
        ],
        out_specs=[
            pl.BlockSpec((C,), lambda i: (0,)),
            pl.BlockSpec((d, C), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((C,), jnp.float32),
            jax.ShapeDtypeStruct((d, C), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((C,), jnp.float32),
            pltpu.VMEM((d, C), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(X, y, w, beta)
