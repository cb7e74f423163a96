"""The float64 reference against the textbook formulas."""

import math

import numpy as np
from scipy.linalg import cho_factor, cho_solve
from scipy.stats import norm

import tinybench  # noqa: F401  (puts the checkout on sys.path)
from bench.reference import TenantRef, eirate, eirate_bf16, rel_gap


def _matern(rng, m):
    x = rng.uniform(size=(m, 3))
    r = np.sqrt(((x[:, None] - x[None]) ** 2).sum(-1)) / 0.5
    s5 = math.sqrt(5) * r
    return 0.04 * (1 + s5 + 5 * r * r / 3) * np.exp(-s5)


def test_incremental_posterior_equals_the_batch_solve():
    rng = np.random.default_rng(0)
    m, jitter = 40, 1e-6
    K = _matern(rng, m)
    mu0 = rng.normal(0, 0.1, m)
    z = rng.normal(0.3, 0.2, m)
    t = TenantRef(0, K, mu0, np.ones(m), z, jitter)
    obs = [3, 17, 5, 30, 22, 8]
    for x in obs:
        t.observe(x, z[x])
    Koo = K[np.ix_(obs, obs)] + jitter * np.eye(len(obs))
    c = cho_factor(Koo, lower=True)
    mu = mu0 + K[:, obs] @ cho_solve(c, z[obs] - mu0[obs])
    var = np.diag(K) - np.einsum("ij,ji->i", K[:, obs],
                                 cho_solve(c, K[obs, :]))
    got_mu, got_var = t.posterior()
    assert np.allclose(got_mu, mu, atol=1e-9)
    assert np.allclose(got_var, np.maximum(var, 0), atol=1e-9)
    assert t.best == max(z[obs])


def test_eirate_is_the_closed_form_expected_improvement_over_cost():
    mu = np.array([0.1, 0.5, -0.2, 0.3])
    var = np.array([0.04, 0.01, 0.09, 0.0])
    cost = np.array([1.0, 2.0, 0.5, 1.0])
    best = 0.2
    sd = np.sqrt(var)
    want = np.empty(4)
    for i in range(3):
        u = (mu[i] - best) / sd[i]
        want[i] = sd[i] * (u * norm.cdf(u) + norm.pdf(u)) / cost[i]
    want[3] = max(mu[3] - best, 0.0) / cost[3]
    assert np.allclose(eirate(mu, var, best, cost), want, rtol=1e-12)
    low = eirate_bf16(mu, var, best, cost)
    assert np.allclose(low, want, rtol=2e-2)
    assert not np.array_equal(low, want)


def test_rel_gap():
    assert rel_gap(2.0, 2.0) == 0.0
    assert rel_gap(2.0, 1.5) == 0.25
    assert rel_gap(2.0, -math.inf) == math.inf
