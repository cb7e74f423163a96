"""Pallas TPU kernel: fused incremental-GP posterior readout.

The incremental engine (repro.core.gp.IncrementalGP) maintains
  W     (k, n)  = L^{-1} K[obs, :]
  alpha (k,)    = L^{-1} (z_obs - mu0_obs)
and the scheduler needs, per decision,
  mu_post  = mu0 + W^T alpha                (matvec, MXU)
  var_post = K_diag - sum_k W[k,:]^2        (column sum-of-squares, VPU)

Reading W twice (matvec + sumsq) doubles HBM traffic on what is a purely
memory-bound O(k*n) pass; this kernel streams each (block_k x block_n) tile
of W through VMEM exactly once, producing both outputs.

Grid: (n_blocks, k_blocks), k innermost (sequential) with two VMEM
accumulators; the mu0/K_diag epilogue runs on the last k block.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _readout_kernel(W_ref, alpha_ref, mu0_ref, kdiag_ref, mu_out, var_out,
                    acc_dot, acc_sq, *, emit_sd: bool = False):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_dot[...] = jnp.zeros_like(acc_dot)
        acc_sq[...] = jnp.zeros_like(acc_sq)

    W = W_ref[...]                                  # (bk, bn)
    a = alpha_ref[:, 0]                             # (bk,)
    acc_dot[...] += jnp.dot(a[None, :], W,
                            preferred_element_type=jnp.float32)
    acc_sq[...] += jnp.sum(W * W, axis=0, keepdims=True)

    @pl.when(j == pl.num_programs(1) - 1)
    def _epilogue():
        mu_out[...] = mu0_ref[...] + acc_dot[...]
        var = jnp.maximum(kdiag_ref[...] - acc_sq[...], 0.0)
        # emit_sd: the EIrate consumer wants sigma, not variance — the sqrt
        # rides the epilogue instead of costing a second (n,) pass
        var_out[...] = jnp.sqrt(var) if emit_sd else var


@functools.partial(jax.jit, static_argnames=("block_n", "block_k", "interpret",
                                             "emit_sd"))
def gp_readout_pallas(
    W: jax.Array,         # (k, n)
    alpha: jax.Array,     # (k,)
    mu0: jax.Array,       # (n,)
    k_diag: jax.Array,    # (n,)
    *,
    block_n: int = 512,
    block_k: int = 512,
    interpret: bool = False,
    emit_sd: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (mu_post (n,), var_post (n,)) — or (mu_post, sd_post) with
    ``emit_sd`` (the fused readout->EIrate pipeline of the sharded scoring
    plane consumes sigma directly)."""
    k, n = W.shape
    bn = min(block_n, max(n, 1))
    bk = min(block_k, max(k, 1))
    pn = math.ceil(n / bn) * bn
    pk = math.ceil(k / bk) * bk

    f32 = jnp.float32
    W_p = jnp.zeros((pk, pn), f32).at[:k, :n].set(W.astype(f32))
    a_p = jnp.zeros((pk, 1), f32).at[:k, 0].set(alpha.astype(f32))
    mu0_p = jnp.zeros((1, pn), f32).at[0, :n].set(mu0.astype(f32))
    kd_p = jnp.zeros((1, pn), f32).at[0, :n].set(k_diag.astype(f32))

    grid = (pn // bn, pk // bk)
    mu_out, var_out = pl.pallas_call(
        functools.partial(_readout_kernel, emit_sd=emit_sd),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bn), lambda i, j: (j, i)),
            pl.BlockSpec((bk, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, pn), f32),
            jax.ShapeDtypeStruct((1, pn), f32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, bn), jnp.float32),
            pltpu.VMEM((1, bn), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(W_p, a_p, mu0_p, kd_p)
    return mu_out[0, :n], var_out[0, :n]
