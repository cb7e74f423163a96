"""Pallas TPU kernel: Mamba2 SSD chunk scan (arXiv:2405.21060).

Fuses, per (batch*head, chunk):

  intra-chunk   y[t] += sum_{s<=t} (C_t.B_s) * exp(lcum_t - lcum_s) * xdt_s
  inter-chunk   y[t] += exp(lcum_t) * (C_t . state)
  state update  state  = exp(l_end) * state + sum_s exp(l_end - lcum_s) B_s (x) xdt_s

where xdt = dt * x (dt folded into the value stream upstream) and
lcum = cumsum(log a) within the chunk.  The (Q x Q) decay-masked score matrix
and the (P x N) recurrent state never leave VMEM; the XLA reference path
(repro.models.ssm) materializes the (B, Q, Q, H) decay tensor in HBM.

Grid: (B*H, chunks) with the chunk axis sequential; the state is VMEM
scratch carried across chunk steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(xdt_ref, b_ref, c_ref, la_ref, y_ref, state, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    xdt = xdt_ref[0, :, :].astype(jnp.float32)          # (Q, P)
    bmat = b_ref[0, :, :].astype(jnp.float32)           # (Q, N)
    cmat = c_ref[0, :, :].astype(jnp.float32)           # (Q, N)
    la = la_ref[0, :, 0].astype(jnp.float32)            # (Q,)
    lcum = jnp.cumsum(la)                               # (Q,)

    # intra-chunk masked decay attention
    scores = jnp.dot(cmat, bmat.T, preferred_element_type=jnp.float32)  # (Q,Q)
    decay = lcum[:, None] - lcum[None, :]
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    w = jnp.where(s_idx <= t_idx, scores * jnp.exp(decay), 0.0)
    y = jnp.dot(w, xdt, preferred_element_type=jnp.float32)             # (Q,P)

    # inter-chunk contribution from the carried state
    y += jnp.exp(lcum)[:, None] * jnp.dot(
        cmat, state[...].T, preferred_element_type=jnp.float32)         # (Q,P)

    # state update
    l_end = lcum[chunk - 1]
    w_state = jnp.exp(l_end - lcum)                                     # (Q,)
    bx = jnp.dot((bmat * w_state[:, None]).T, xdt,
                 preferred_element_type=jnp.float32)                    # (N,P)
    state[...] = jnp.exp(l_end) * state[...] + bx.T                     # (P,N)

    y_ref[0, :, :] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_pallas(
    x: jax.Array,        # (B, S, H, P)
    dt: jax.Array,       # (B, S, H)  discretization step (softplus'd, clipped)
    log_a: jax.Array,    # (B, S, H)  per-step log decay (dt * A, <= 0)
    b: jax.Array,        # (B, S, N)
    c: jax.Array,        # (B, S, N)
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Returns the SSD mix y (B, S, H, P) (without the D*x skip term)."""
    B, S, H, P = x.shape
    N = b.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q

    xdt = (x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None])
    # head-major: (B*H, S, ...)
    xdt_h = xdt.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    la_h = log_a.astype(jnp.float32).transpose(0, 2, 1).reshape(B * H, S, 1)

    def bc_map(g, ci):
        return (g // H, ci, 0)

    out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=Q),
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, Q, N), bc_map),
            pl.BlockSpec((1, Q, N), bc_map),
            pl.BlockSpec((1, Q, 1), lambda g, ci: (g, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, Q, P), lambda g, ci: (g, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, P), jnp.float32),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xdt_h, b, c, la_h)
    return out.reshape(B, H, S, P).transpose(0, 2, 1, 3)
