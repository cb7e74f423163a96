"""Pallas TPU kernel: causal GQA flash attention (forward).

TPU adaptation of the paper-adjacent standard (DESIGN.md §3): block-streamed
keys/values with online softmax, block-causal *skipping* (the XLA reference
path masks but still computes all (i, j) block pairs — 2x wasted MXU work),
and optional sliding-window skipping (H2O-Danube).  Layout is head-major
(BH, S, D) so each grid step works on MXU-aligned (block_q x D) / (block_k x
D) tiles resident in VMEM.

Grid: (B*Hq, q_blocks, kv_blocks), kv innermost (sequential); accumulators
(acc, row-max m, row-sum l) live in VMEM scratch across kv steps.  GQA maps
query head h to KV head h // (Hq // Hkv) in the BlockSpec index maps.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc, m_i, l_i, *,
                  scale: float, block_q: int, block_k: int,
                  causal: bool, window: int | None):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_i[...] = jnp.full_like(m_i, NEG_INF)
        l_i[...] = jnp.zeros_like(l_i)

    q_first = i * block_q
    q_last = q_first + block_q - 1
    k_first = j * block_k
    k_last = k_first + block_k - 1

    needed = True
    if causal:
        needed = jnp.logical_and(needed, k_first <= q_last)
    if window is not None:
        needed = jnp.logical_and(needed, k_last > q_first - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, :, :].astype(jnp.float32)          # (bq, D)
        k = k_ref[0, :, :].astype(jnp.float32)          # (bk, D)
        v = v_ref[0, :, :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        q_pos = q_first + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_first + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), bool)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_i[:, 0], jnp.max(s, axis=1))          # (bq,)
        corr = jnp.exp(m_i[:, 0] - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        l_i[:, 0] = l_i[:, 0] * corr + jnp.sum(p, axis=1)
        acc[...] = acc[...] * corr[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_i[:, 0] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        denom = jnp.maximum(l_i[:, 0], 1e-30)
        o_ref[0, :, :] = (acc[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention_pallas(
    q: jax.Array,            # (B, S, Hq, D)
    k: jax.Array,            # (B, S, Hkv, D)
    v: jax.Array,            # (B, S, Hkv, D)
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Returns (B, S, Hq, D) attention output."""
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    bq = min(block_q, S)
    bk = min(block_k, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    scale = 1.0 / math.sqrt(D)

    # head-major flattening: (B*Hq, S, D) / (B*Hkv, S, D)
    qh = q.transpose(0, 2, 1, 3).reshape(B * Hq, S, D)
    kh = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    vh = v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)

    def kv_head(b, i, j):
        return ((b // Hq) * Hkv + (b % Hq) // group, j, 0)

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=bq, block_k=bk,
        causal=causal, window=window)

    out = pl.pallas_call(
        kernel,
        grid=(B * Hq, S // bq, S // bk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), kv_head),
            pl.BlockSpec((1, bk, D), kv_head),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qh, kh, vh)
    return out.reshape(B, Hq, S, D).transpose(0, 2, 1, 3)
