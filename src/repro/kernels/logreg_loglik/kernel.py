"""Pallas TPU kernel: the logistic log-likelihood and its gradient in one pass.

The likelihood reads each row only through ``u_i = s_i · x_i`` (s = 2y − 1):

    ℓ(β)  = Σ_i log σ(βᵀu_i)        ∇ℓ(β) = Σ_i σ(−βᵀu_i) · u_i

so the kernel takes the shard as one lane-dense array ``ut = (s ⊙ X)ᵀ`` of
shape (d, N): rows on lanes, features on sublanes (d = 54 pads to 56
sublanes, not to 128 lanes). A grid step DMAs a (d, block_n) block into VMEM;
the body walks it one 128-lane tile at a time (``sub`` lanes a loop step)
and, on the VPU in float32,

- reduces over sublanes for v = βᵀu (one value a row),
- forms log σ(v) and σ(−v),
- accumulates σ(−v)·u and log σ(v) into (d, 128) and (1, 128) carries,

so both reductions come from the one read of the block; the carries reduce
across lanes once per block. The columns past N in the last block hold
whatever the DMA left there; the kernel masks them, so the caller never pads
``ut``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _logreg_kernel(beta_ref, ut_ref, loglik_ref, grad_ref, *, n: int,
                   block_n: int, sub: int):
    i = pl.program_id(0)
    d = ut_ref.shape[0]
    beta = jnp.broadcast_to(beta_ref[...], (d, LANES))  # hoisted

    def chunk(start, carry, n_valid=None):
        """``sub`` columns from ``start``, one 128-lane tile at a time; with
        ``n_valid``, only that many count (the tail of the last block)."""
        acc_l, acc_g = carry
        for t in range(sub // LANES):
            u = ut_ref[:, pl.ds(start + t * LANES, LANES)]  # (d, 128)
            if n_valid is not None:  # garbage in, zero out
                col = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
                valid = col + t * LANES < n_valid
                u = jnp.where(valid, u, 0.0)
            v = jnp.sum(u * beta, axis=0, keepdims=True)  # (1, 128) = βᵀu
            ll = -jnp.logaddexp(0.0, -v)  # log σ(v), stably
            if n_valid is not None:
                ll = jnp.where(valid, ll, 0.0)
            acc_l = acc_l + ll
            acc_g = acc_g + u * jax.nn.sigmoid(-v)
        return acc_l, acc_g

    def block(n_cols: int):
        """Accumulate the block's first ``n_cols`` columns (static)."""
        full, tail = divmod(n_cols, sub)
        carry = (jnp.zeros((1, LANES), jnp.float32),
                 jnp.zeros((d, LANES), jnp.float32))
        carry = jax.lax.fori_loop(
            0, full,
            lambda j, c: chunk(pl.multiple_of(j * sub, sub), c),
            carry,
        )
        if tail:
            carry = chunk(full * sub, carry, n_valid=tail)
        acc_l, acc_g = carry
        return (jnp.sum(acc_l, axis=1, keepdims=True),
                jnp.sum(acc_g, axis=1, keepdims=True))

    @pl.when(i == 0)
    def _init():
        loglik_ref[...] = jnp.zeros_like(loglik_ref)
        grad_ref[...] = jnp.zeros_like(grad_ref)

    def add(n_cols):
        l, g = block(n_cols)
        loglik_ref[...] += l
        grad_ref[...] += g

    n_blocks = -(-n // block_n)
    last = n - (n_blocks - 1) * block_n  # columns of the last block
    if n_blocks == 1 or last == block_n:  # one shape of block
        add(last)
    else:
        pl.when(i < n_blocks - 1)(lambda: add(block_n))
        pl.when(i == n_blocks - 1)(lambda: add(last))


@functools.partial(jax.jit, static_argnames=("block_n", "sub", "interpret"))
def logreg_loglik_grad_kernel(
    ut: jnp.ndarray,  # (d, N) float32: (s ⊙ X)ᵀ
    beta: jnp.ndarray,  # (d, 1) float32
    *,
    block_n: int,
    sub: int,
    interpret: bool = False,
):
    """``(ℓ (1, 1), ∇ℓ (d, 1))``; ``block_n`` a multiple of ``sub``, and
    ``sub`` of 128. Under ``vmap`` Pallas adds the batch as a parallel
    leading grid axis."""
    d, n = ut.shape
    kernel = functools.partial(_logreg_kernel, n=n, block_n=block_n, sub=sub)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, block_n),),
        in_specs=[
            pl.BlockSpec((d, 1), lambda i: (0, 0)),  # β resident
            pl.BlockSpec((d, block_n), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((d, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((d, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(beta, ut)
