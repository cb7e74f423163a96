"""Pallas TPU kernel: fused multi-tenant EIrate scoring (eqs. 3-6).

The scheduler's hot loop evaluates, for every candidate model x and every
tenant i owning it,

    EI_i(x)   = sigma(x) * tau((mu(x) - best_i) / sigma(x)),
    score(x)  = sum_i member[i, x] * EI_i(x) / c(x),   (-inf if selected)

an (N x n) pass that is pure VPU work (exp/divide) plus a tenant-axis reduction.
At service scale (|L| ~ 10^4-10^5 models, N ~ 10^3 tenants) the naive path
materializes the (N, n) EI matrix in HBM; this kernel tiles it into VMEM
(block_users x block_models tiles, 128-lane aligned) and accumulates the
tenant sum in-register, writing only the (n,) score vector.

Grid: (models_blocks, user_blocks); the user axis is the innermost
(sequential) dimension, accumulating into the output block, with the
cost/selected epilogue applied on the final user block.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_LARGE = -1e30
_INV_SQRT2 = 0.7071067811865476
_INV_SQRT_2PI = 0.3989422804014327

# Chebyshev fit of erfc (Press et al., Numerical Recipes, 2nd ed., §6.2
# "erfcc"): erfc(x) = t * exp(-x^2 + P(t)), t = 1 / (1 + x/2), x >= 0, with
# fractional error below 1.2e-7 everywhere -- Mosaic lowers no erf/erfc
# primitive, and a relative (not absolute) bound keeps Phi(u) accurate deep
# in the lower tail, where EI ranks the unpromising candidates.
_ERFC_COEFFS = (-1.26551223, 1.00002368, 0.37409196, 0.09678418,
                -0.18628806, 0.27886807, -1.13520398, 1.48851587,
                -0.82215223, 0.17087277)


def _norm_cdf(u):
    """Phi(u) from exp and division only (see ``_ERFC_COEFFS``)."""
    x = jnp.abs(u) * _INV_SQRT2
    t = 1.0 / (1.0 + 0.5 * x)
    poly = _ERFC_COEFFS[-1]
    for c in _ERFC_COEFFS[-2::-1]:
        poly = c + t * poly
    half_erfc = 0.5 * t * jnp.exp(poly - x * x)    # Phi(-|u|)
    return jnp.where(u < 0, half_erfc, 1.0 - half_erfc)


def _tau_terms(u):
    """tau(u) = u * Phi(u) + phi(u)."""
    pdf = jnp.exp(-0.5 * u * u) * _INV_SQRT_2PI
    return u * _norm_cdf(u) + pdf


def _ei_partial(mu_ref, sigma_ref, best_ref, member_ref):
    """One (bN, bn) tile's tenant-axis partial EI sum -> (bn,)."""
    mu = mu_ref[0, :]                       # (bn,)
    sg = sigma_ref[0, :]
    best = best_ref[:, 0]                   # (bN,)
    mem = member_ref[...]                   # (bN, bn)

    safe = jnp.where(sg > 0, sg, 1.0)
    u = (mu[None, :] - best[:, None]) / safe[None, :]
    ei = safe[None, :] * _tau_terms(u)
    ei_degenerate = jnp.maximum(mu[None, :] - best[:, None], 0.0)
    ei = jnp.where(sg[None, :] > 0, ei, ei_degenerate)
    return jnp.sum(ei * mem, axis=0)        # (bn,)


def _ei_kernel(mu_ref, sigma_ref, cost_ref, selected_ref, best_ref, member_ref,
               out_ref):
    j = pl.program_id(1)
    partial = _ei_partial(mu_ref, sigma_ref, best_ref, member_ref)

    @pl.when(j == 0)
    def _init():
        out_ref[0, :] = partial

    @pl.when(j > 0)
    def _acc():
        out_ref[0, :] += partial

    @pl.when(j == pl.num_programs(1) - 1)
    def _epilogue():
        total = out_ref[0, :]
        score = total / cost_ref[0, :]
        out_ref[0, :] = jnp.where(selected_ref[0, :] > 0, NEG_LARGE, score)


def _ei_classes_kernel(mu_ref, sigma_ref, cost_ref, selected_ref, best_ref,
                       member_ref, out_ref):
    """The EIrate kernel generalized to a (C, n) *cost matrix* — one row per
    device class (DESIGN.md §11).  The tenant-axis EI sum is accumulated
    ONCE (into row 0 of the output block) and the final-tenant epilogue
    fans it out against every class's cost row, so a C-class scoring pass
    reads the (N, n) membership tile exactly as often as the 1-class one."""
    j = pl.program_id(1)
    partial = _ei_partial(mu_ref, sigma_ref, best_ref, member_ref)

    @pl.when(j == 0)
    def _init():
        out_ref[0, :] = partial

    @pl.when(j > 0)
    def _acc():
        out_ref[0, :] += partial

    @pl.when(j == pl.num_programs(1) - 1)
    def _epilogue():
        total = out_ref[0, :]
        sel = selected_ref[0, :] > 0
        # row 0 holds the accumulator: write it last.  A non-finite cost
        # (memory gate) is a hard exclusion, same as the selected mask.
        for c in range(cost_ref.shape[0] - 1, -1, -1):
            row = cost_ref[c, :]
            out_ref[c, :] = jnp.where(sel | ~jnp.isfinite(row),
                                      NEG_LARGE, total / row)


def _block_topk(score_row, k: int, block_base):
    """Block-local top-k of a (1, bn) score tile, VPU-only: k unrolled
    max / min-index-at-max / mask rounds (no sort — Mosaic has no top_k).
    Equal values resolve to the lowest index, matching both ``jnp.argmax``
    and ``jax.lax.top_k`` ordering — the sharded scoring plane's exactness
    argument (DESIGN.md §10) leans on this."""
    bn = score_row.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    work = score_row
    vals = jnp.full((1, k), NEG_LARGE, jnp.float32)
    idxs = jnp.zeros((1, k), jnp.int32)
    # (1, 1) keepdims reductions and a lane select per round: Mosaic
    # lowers neither 0-d reductions nor stacking scalars into a vector
    for r in range(k):
        m = jnp.max(work, axis=1, keepdims=True)
        idx = jnp.min(jnp.where(work == m, iota, jnp.int32(bn)), axis=1,
                      keepdims=True)
        vals = jnp.where(slot == r, m, vals)
        idxs = jnp.where(slot == r, jnp.minimum(idx, bn - 1), idxs)
        work = jnp.where(iota == idx, NEG_LARGE, work)
    return vals, idxs + block_base


def _ei_topk_kernel(mu_ref, sigma_ref, cost_ref, selected_ref, best_ref,
                    member_ref, out_ref, topv_ref, topi_ref, *, k: int):
    """The EIrate kernel with a block-local top-k epilogue: alongside the
    (n,) scores, each model block emits its k best (value, global index)
    candidates, so a sharded caller reduces (num_blocks, k) candidates
    instead of re-reading the whole score vector."""
    _ei_kernel(mu_ref, sigma_ref, cost_ref, selected_ref, best_ref,
               member_ref, out_ref)
    i = pl.program_id(0)
    j = pl.program_id(1)
    bn = out_ref.shape[1]

    @pl.when(j == pl.num_programs(1) - 1)
    def _topk_epilogue():
        vals, idxs = _block_topk(out_ref[0:1, :], k, i * bn)
        topv_ref[0] = vals
        topi_ref[0] = idxs


def _pad_inputs(mu, sigma, best, membership, cost, selected, bn, bN):
    n, N = mu.shape[0], best.shape[0]
    pn = math.ceil(n / bn) * bn
    pN = math.ceil(N / bN) * bN
    f32 = jnp.float32
    mu_p = jnp.zeros((1, pn), f32).at[0, :n].set(mu.astype(f32))
    sg_p = jnp.zeros((1, pn), f32).at[0, :n].set(sigma.astype(f32))
    cost_p = jnp.ones((1, pn), f32).at[0, :n].set(cost.astype(f32))
    sel_p = jnp.ones((1, pn), f32).at[0, :n].set(selected.astype(f32))
    best_p = jnp.zeros((pN, 1), f32).at[:N, 0].set(best.astype(f32))
    mem_p = jnp.zeros((pN, pn), f32).at[:N, :n].set(membership.astype(f32))
    return (mu_p, sg_p, cost_p, sel_p, best_p, mem_p), pn, pN


@functools.partial(jax.jit, static_argnames=("block_models", "block_users", "interpret"))
def eirate_pallas(
    mu: jax.Array,           # (n,)
    sigma: jax.Array,        # (n,)
    best: jax.Array,         # (N,)
    membership: jax.Array,   # (N, n) bool/float
    cost: jax.Array,         # (n,)
    selected: jax.Array,     # (n,) bool
    *,
    block_models: int = 256,
    block_users: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Returns (n,) EIrate scores, -1e30 at selected models."""
    n = mu.shape[0]
    N = best.shape[0]
    bn = min(block_models, max(n, 1))
    bN = min(block_users, max(N, 1))
    (mu_p, sg_p, cost_p, sel_p, best_p, mem_p), pn, pN = _pad_inputs(
        mu, sigma, best, membership, cost, selected, bn, bN)

    grid = (pn // bn, pN // bN)
    out = pl.pallas_call(
        _ei_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
            pl.BlockSpec((bN, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((bN, bn), lambda i, j: (j, i)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, pn), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(mu_p, sg_p, cost_p, sel_p, best_p, mem_p)
    return out[0, :n]


@functools.partial(jax.jit, static_argnames=(
    "k", "block_models", "block_users", "interpret"))
def eirate_topk_pallas(
    mu: jax.Array,           # (n,)
    sigma: jax.Array,        # (n,)
    best: jax.Array,         # (N,)
    membership: jax.Array,   # (N, n) bool/float
    cost: jax.Array,         # (n,)
    selected: jax.Array,     # (n,) bool
    *,
    k: int = 4,
    block_models: int = 256,
    block_users: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """EIrate scoring with the block-local top-k epilogue: returns the
    global top-k as ``(values (k,), indices (k,))``, ties broken by lowest
    index (exactly ``jax.lax.top_k`` over the full score vector).  Each
    model block emits its k best candidates in VMEM; the host-side reduce
    touches only (num_blocks, k) — the shape the sharded scoring plane
    all-gathers (DESIGN.md §10)."""
    n = mu.shape[0]
    N = best.shape[0]
    bn = min(block_models, max(n, 1))
    bN = min(block_users, max(N, 1))
    kb = min(k, bn)          # a block cannot yield more candidates than bn
    (mu_p, sg_p, cost_p, sel_p, best_p, mem_p), pn, pN = _pad_inputs(
        mu, sigma, best, membership, cost, selected, bn, bN)

    grid = (pn // bn, pN // bN)
    _, topv, topi = pl.pallas_call(
        functools.partial(_ei_topk_kernel, k=kb),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
            pl.BlockSpec((bN, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((bN, bn), lambda i, j: (j, i)),
        ],
        # (num_blocks, 1, kb) with (1, 1, kb) blocks: the last two block
        # dims equal the array's, the layout Mosaic accepts for a kb < 128
        out_specs=[
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
            pl.BlockSpec((1, 1, kb), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, kb), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, pn), jnp.float32),
            jax.ShapeDtypeStruct((pn // bn, 1, kb), jnp.float32),
            jax.ShapeDtypeStruct((pn // bn, 1, kb), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(mu_p, sg_p, cost_p, sel_p, best_p, mem_p)

    flatv = topv.reshape(-1)
    flati = topi.reshape(-1)
    # candidates in padding columns are inert; keep shape >= k regardless
    flatv = jnp.where(flati < n, flatv, NEG_LARGE)
    if flatv.shape[0] < k:
        pad = k - flatv.shape[0]
        flatv = jnp.concatenate([flatv, jnp.full(pad, NEG_LARGE, jnp.float32)])
        flati = jnp.concatenate([flati, jnp.zeros(pad, jnp.int32)])
    v, pos = jax.lax.top_k(flatv, k)
    return v, flati[pos]


@functools.partial(jax.jit, static_argnames=("block_models", "block_users",
                                             "interpret"))
def eirate_classes_pallas(
    mu: jax.Array,           # (n,)
    sigma: jax.Array,        # (n,)
    best: jax.Array,         # (N,)
    membership: jax.Array,   # (N, n) bool/float
    cost_matrix: jax.Array,  # (C, n) per-device-class c(x, d)
    selected: jax.Array,     # (n,) bool
    *,
    block_models: int = 256,
    block_users: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Returns (C, n) per-class EIrate scores, -1e30 at selected models —
    the elastic device plane's 2-D (free devices x live models) matrix in
    one kernel launch (tenant sum accumulated once, fanned out per class)."""
    n = mu.shape[0]
    N = best.shape[0]
    C = cost_matrix.shape[0]
    bn = min(block_models, max(n, 1))
    bN = min(block_users, max(N, 1))
    (mu_p, sg_p, _, sel_p, best_p, mem_p), pn, pN = _pad_inputs(
        mu, sigma, best, membership, jnp.ones_like(mu), selected, bn, bN)
    cost_p = jnp.ones((C, pn), jnp.float32).at[:, :n].set(
        cost_matrix.astype(jnp.float32))

    grid = (pn // bn, pN // bN)
    out = pl.pallas_call(
        _ei_classes_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
            pl.BlockSpec((C, bn), lambda i, j: (0, i)),
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
            pl.BlockSpec((bN, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((bN, bn), lambda i, j: (j, i)),
        ],
        out_specs=pl.BlockSpec((C, bn), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((C, pn), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(mu_p, sg_p, cost_p, sel_p, best_p, mem_p)
    return out[:, :n]
