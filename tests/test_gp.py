"""GP posterior engines vs the closed form (Supplemental A)."""

import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

import repro.core.gp as gp
from repro.core.control_plane import ControlPlane
from repro.core.gp import BlockIncrementalGP, IncrementalGP, posterior_masked
from repro.obs import Tracer

from conftest import random_psd


def closed_form(K, mu0, z, obs, jitter=1e-6):
    obs = list(obs)
    Koo = K[np.ix_(obs, obs)] + jitter * np.eye(len(obs))
    Kxo = K[:, obs]
    sol = np.linalg.solve(Koo, z[obs] - mu0[obs])
    mu = mu0 + Kxo @ sol
    var = np.diag(K) - np.einsum("ij,jk,ik->i", Kxo, np.linalg.inv(Koo), Kxo)
    return mu, np.maximum(var, 0.0)


@pytest.mark.parametrize("n,k", [(8, 3), (20, 12), (5, 5)])
def test_masked_matches_closed_form(rng, n, k):
    K = random_psd(rng, n)
    mu0 = rng.standard_normal(n)
    z = rng.standard_normal(n)
    obs = rng.choice(n, size=k, replace=False)
    mask = np.zeros(n, bool)
    mask[obs] = True
    mu, var = posterior_masked(
        jnp.asarray(K, jnp.float32), jnp.asarray(mu0, jnp.float32),
        jnp.asarray(z, jnp.float32), jnp.asarray(mask))
    mu_ref, var_ref = closed_form(K, mu0, z, obs)
    np.testing.assert_allclose(np.asarray(mu), mu_ref, atol=2e-4)
    np.testing.assert_allclose(np.asarray(var), var_ref, atol=2e-4)


def test_incremental_matches_masked_any_order(rng):
    n = 15
    K = random_psd(rng, n)
    mu0 = rng.standard_normal(n)
    z = rng.standard_normal(n)
    for order_seed in range(3):
        order = np.random.default_rng(order_seed).permutation(n)[:9]
        gp = IncrementalGP(K.astype(np.float32), mu0.astype(np.float32))
        for i in order:
            gp.observe(int(i), float(z[i]))
        mu_i, var_i = gp.posterior()
        mu_ref, var_ref = closed_form(K, mu0, z, list(order))
        np.testing.assert_allclose(np.asarray(mu_i), mu_ref, atol=3e-4)
        np.testing.assert_allclose(np.asarray(var_i), var_ref, atol=3e-4)


def test_posterior_interpolates_observations(rng):
    n = 10
    K = random_psd(rng, n)
    z = rng.standard_normal(n)
    gp = IncrementalGP(K.astype(np.float32), np.zeros(n, np.float32))
    for i in (2, 5, 7):
        gp.observe(i, float(z[i]))
    mu, var = gp.posterior()
    for i in (2, 5, 7):
        assert abs(float(mu[i]) - z[i]) < 1e-2
        assert float(var[i]) < 1e-2


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 10), st.integers(0, 1_000_000))
def test_variance_never_increases(n, seed):
    """Conditioning reduces (marginal) variance — the property Theorem 2's
    proof leans on (eq. 13)."""
    rng = np.random.default_rng(seed)
    K = random_psd(rng, n)
    z = rng.standard_normal(n)
    gp = IncrementalGP(K.astype(np.float32), np.zeros(n, np.float32))
    prev_var = np.asarray(gp.posterior()[1])
    order = rng.permutation(n)
    for i in order:
        gp.observe(int(i), float(z[i]))
        var = np.asarray(gp.posterior()[1])
        assert (var <= prev_var + 1e-3).all()
        prev_var = var


def test_duplicate_observation_rejected(rng):
    K = random_psd(rng, 4)
    gp = IncrementalGP(K.astype(np.float32), np.zeros(4, np.float32))
    gp.observe(1, 0.5)
    with pytest.raises(ValueError):
        gp.observe(1, 0.7)


# ---- W sized by its observed rows; one prior shared by several blocks -------

def _grow_every(monkeypatch, step: int) -> None:
    """Row capacities ``step * 2**j + 8``, so that a small block grows."""
    monkeypatch.setattr(gp, "ROW_STEP", step)


def _full_buffer_folds(K, mu0, order, z):
    """The (m, m) layout driven through the same ``_append_step``: after
    each fold its pivot and the posterior ``_readout`` gives."""
    m = K.shape[0]
    K, mu0 = jnp.asarray(K), jnp.asarray(mu0)
    W, alpha, acc = (jnp.zeros((m, m), K.dtype), jnp.zeros(m, K.dtype),
                     jnp.zeros(m, K.dtype))
    jitter = jnp.asarray(gp.DEFAULT_JITTER, K.dtype)
    out = []
    for k, i in enumerate(order):
        W, alpha, acc, d2 = gp._append_step(
            W, alpha, acc, K[i], jnp.asarray(i),
            jnp.asarray(z[i], K.dtype), mu0[i], jnp.asarray(k), jitter)
        mu, var = gp._readout(W, alpha, mu0, jnp.diag(K), acc,
                              jnp.asarray(k + 1))
        out.append((np.asarray(d2), np.asarray(mu), np.asarray(var)))
    return out


@pytest.mark.parametrize("order_seed", [0, 1])
def test_growing_rows_match_a_full_buffer_bit_for_bit(monkeypatch, rng,
                                                      order_seed):
    _grow_every(monkeypatch, 8)
    m = 50
    assert gp.row_capacities(m) == [16, 24, 40, 50]
    K = random_psd(rng, m, 0.04).astype(np.float32)
    mu0 = rng.standard_normal(m).astype(np.float32)
    z = rng.standard_normal(m)
    order = np.random.default_rng(order_seed).permutation(m)[:45]
    full = _full_buffer_folds(K, mu0, order, z)
    grown = IncrementalGP(K, mu0)
    for (d2, mu, var), i in zip(full, order):
        grown.observe(int(i), float(z[i]))
        g_mu, g_var = grown.posterior()
        assert np.array_equal(np.asarray(grown.last_d2), d2)
        assert np.array_equal(np.asarray(g_mu), mu)
        assert np.array_equal(np.asarray(g_var), var)
    assert grown.resource_stats()["rows"] == 50


def test_growing_rows_agree_with_posterior_masked(monkeypatch, rng):
    _grow_every(monkeypatch, 8)
    m = 50
    K = random_psd(rng, m, 0.04).astype(np.float32)
    mu0 = np.zeros(m, np.float32)
    z = 0.2 * rng.standard_normal(m)
    order = rng.permutation(m)[:40]
    grown = IncrementalGP(K, mu0)
    for i in order:
        grown.observe(int(i), float(z[i]))
    mask = np.zeros(m, bool)
    mask[order] = True
    mu_ref, var_ref = posterior_masked(
        jnp.asarray(K), jnp.asarray(mu0), jnp.asarray(z, jnp.float32),
        jnp.asarray(mask))
    mu, var = grown.posterior()
    full = _full_buffer_folds(K, mu0, order, z)[-1]
    for got in ((mu, var), full[1:]):
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(mu_ref),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(var_ref),
                                   atol=1e-5)


def _shared_pair(rng, m: int = 6):
    """A control plane holding two tenants given one prior object, and a
    third given an equal copy."""
    K = random_psd(rng, m, 0.04)
    cp = ControlPlane(np.random.default_rng(0), model_capacity=64)
    tr = Tracer()
    cp.set_tracer(tr)
    handles = []
    for i, block in enumerate((K, K, K.copy())):
        tr.begin_trace(i)
        handles.append(cp.add_tenant(block, np.zeros(m), np.ones(m)))
    return cp, tr, handles, K


def _engine(cp, handle):
    return cp.gp._engines[cp._block_ids[handle.tenant_id]]


def test_one_prior_object_is_one_device_array(rng):
    cp, tr, (a, b, c), K = _shared_pair(rng)
    assert _engine(cp, a).K is _engine(cp, b).K
    assert _engine(cp, c).K is not _engine(cp, a).K     # contents not read
    admits = [r for r in tr.records() if r["name"] == "admit"]
    assert [r["counts"]["prior_shared"] for r in admits] == [0, 1, 0]
    # the admission's own upload (its mirrors count on their own span):
    # the kernel only where it is not shared, the mean and the jitter
    kernel, mean = K.size * 4, (K.shape[0] + 1) * 4
    assert [r["counts"]["h2d_bytes"] for r in admits] == [
        kernel + mean, mean, kernel + mean]


def test_retiring_one_sharer_keeps_the_prior_alive_and_correct(rng):
    cp, _, (a, b, _), K = _shared_pair(rng)
    prior = _engine(cp, b).K
    cp.retire_tenant(a.tenant_id)
    assert _engine(cp, b).K is prior
    z = {0: 0.1, 3: -0.2}
    for li, v in z.items():
        cp.record_start(int(b.models[li]))
        cp.record_observation(int(b.models[li]), v)
    mu, var = cp.gp.posterior()
    z_all = np.zeros(K.shape[0])
    z_all[list(z)] = list(z.values())
    mu_ref, var_ref = closed_form(K, np.zeros(K.shape[0]), z_all, list(z))
    ids = b.models
    np.testing.assert_allclose(np.asarray(mu)[ids], mu_ref, atol=3e-5)
    np.testing.assert_allclose(np.asarray(var)[ids], var_ref, atol=3e-5)


def test_retiring_the_last_sharer_frees_the_prior(rng):
    cp, _, (a, b, c), _ = _shared_pair(rng)
    prior = weakref.ref(_engine(cp, a).K)
    cp.retire_tenant(a.tenant_id)
    gc.collect()
    assert prior() is not None
    cp.retire_tenant(b.tenant_id)
    gc.collect()
    assert prior() is None
    assert cp.gp.resource_stats()["priors"] == 1          # c's own


def test_resource_stats_count_a_shared_prior_once_and_W_by_rows(
        monkeypatch, rng):
    _grow_every(monkeypatch, 2)
    m, item = 12, 4                          # rows 10, then 12
    K = random_psd(rng, m, 0.04)
    g = BlockIncrementalGP.empty()
    a = g.add_block(np.arange(m), K, np.zeros(m))
    g.add_block(np.arange(m, 2 * m), K, np.zeros(m))

    def block(rows):
        return (rows * m + 3 * m) * item

    stats = g.resource_stats()
    assert stats["priors"] == 1
    assert stats["blocks"][a]["rows"] == 10
    assert stats["blocks"][a]["alloc_bytes"] == block(10) + m * m * item
    assert stats["alloc_bytes"] == 2 * block(10) + m * m * item
    for li in range(11):
        g.observe(li, 0.1 * li)
    stats = g.resource_stats()
    assert stats["blocks"][a]["rows"] == 12
    assert stats["alloc_bytes"] == block(12) + block(10) + m * m * item


def test_no_compile_as_W_grows(monkeypatch, rng):
    """Every row capacity is compiled as the block is admitted (the fold)
    and as the pool is uploaded (the readout): after the first fold and
    flush, growing W through each capacity compiles nothing."""
    _grow_every(monkeypatch, 4)
    m = 37                                   # rows 12, 20, 36, 37
    g = BlockIncrementalGP.empty()
    g.ensure_capacity(64)
    g.add_block(np.arange(m), random_psd(rng, m, 0.04), np.zeros(m))
    order = rng.permutation(m)
    g.observe(int(order[0]), 0.1)
    g.flush_device()
    compiles = []

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        for i in order[1:]:
            g.observe(int(i), 0.1)
            jax.block_until_ready(g.flush_device())
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert g.resource_stats()["blocks"][0]["rows"] == m
    assert compiles == []
