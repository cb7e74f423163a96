"""Jit'd public entry points for the Pallas kernels.

Each op picks interpret mode by platform: the Pallas TPU kernels are the
target implementation and run compiled on a TPU; elsewhere they run under
``interpret=True`` for correctness validation, because Mosaic does not
lower on the CPU backend.  The kernel entry points themselves default to
``interpret=False``, so a caller that bypasses this module on a TPU never
runs the interpreter by accident.  ``use_pallas=False`` selects the XLA
reference instead.
"""

from __future__ import annotations

import jax

from . import ref
from .ei_score import eirate_classes_pallas, eirate_pallas, eirate_topk_pallas
from .flash_attention import flash_attention_pallas
from .gp_readout import gp_readout_pallas
from .ssd import ssd_pallas


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def eirate(mu, sigma, best, membership, cost, selected, *, use_pallas=True,
           **kw):
    if not use_pallas:
        return ref.eirate_ref(mu, sigma, best, membership, cost, selected)
    kw.setdefault("interpret", _interpret_default())
    return eirate_pallas(mu, sigma, best, membership, cost, selected, **kw)


def eirate_topk(mu, sigma, best, membership, cost, selected, *, k=4,
                use_pallas=True, **kw):
    """Global EIrate top-k as (values (k,), indices (k,)), lowest-index
    tie-break — the kernel path uses the block-local top-k epilogue so only
    (num_blocks, k) candidates leave VMEM."""
    if not use_pallas:
        return ref.eirate_topk_ref(mu, sigma, best, membership, cost,
                                   selected, k=k)
    kw.setdefault("interpret", _interpret_default())
    return eirate_topk_pallas(mu, sigma, best, membership, cost, selected,
                              k=k, **kw)


def eirate_classes(mu, sigma, best, membership, cost_matrix, selected, *,
                   use_pallas=True, **kw):
    """(C, n) per-device-class EIrate scores (cost_matrix is (C, n)) — the
    elastic device plane's joint-assignment scoring pass (DESIGN.md §11).
    The kernel accumulates the tenant EI sum once and fans it out per class."""
    if not use_pallas:
        return ref.eirate_classes_ref(mu, sigma, best, membership,
                                      cost_matrix, selected)
    kw.setdefault("interpret", _interpret_default())
    return eirate_classes_pallas(mu, sigma, best, membership, cost_matrix,
                                 selected, **kw)


def gp_readout(W, alpha, mu0, k_diag, *, use_pallas=True, emit_sd=False, **kw):
    if not use_pallas:
        import jax.numpy as jnp
        mu, var = ref.gp_readout_ref(W, alpha, mu0, k_diag)
        return (mu, jnp.sqrt(var)) if emit_sd else (mu, var)
    kw.setdefault("interpret", _interpret_default())
    return gp_readout_pallas(W, alpha, mu0, k_diag, emit_sd=emit_sd, **kw)


def flash_attention(q, k, v, *, causal=True, window=None, use_pallas=True, **kw):
    if not use_pallas:
        return ref.attention_ref(q, k, v, causal=causal, window=window)
    kw.setdefault("interpret", _interpret_default())
    return flash_attention_pallas(q, k, v, causal=causal, window=window, **kw)


def ssd_mix(x, dt, log_a, b, c, *, use_pallas=True, **kw):
    if not use_pallas:
        return ref.ssd_ref(x, dt, log_a, b, c)
    kw.setdefault("interpret", _interpret_default())
    return ssd_pallas(x, dt, log_a, b, c, **kw)
