"""The benchmark's own traffic generator."""

import functools
import itertools

import numpy as np
import pytest

import tinybench  # noqa: F401  (puts the checkout on sys.path)
from bench.workload import hamming_factors, kron_apply, make_trace, mean_cost

CHURN = {
    "arrivals": {"kind": "poisson", "rate": 2.0, "count": 60},
    "sessions": {"kind": "pareto", "alpha": 1.5, "scale": 10.0},
    "candidates": {"kind": "zipf", "s": 1.6, "min": 2, "max": 16},
    "space": {"dims": 1, "points": "linspace"},
    "kernel": {"kind": "matern52", "length_scale": 0.2, "variance": 0.04},
    "cost": {"kind": "uniform"},
}
CUBE = {
    "arrivals": {"kind": "at_start", "count": 3},
    "sessions": {"kind": "none"},
    "candidates": {"kind": "fixed", "count": 50},
    "space": {"dims": 7, "points": "uniform"},
    "kernel": {"kind": "matern52", "length_scale": 0.5, "variance": 0.04},
    "cost": {"kind": "lognormal", "sigma": 0.5},
}
GRID = {
    "arrivals": {"kind": "at_start", "count": 3},
    "sessions": {"kind": "none"},
    "candidates": {"kind": "fixed", "count": 27},
    "space": {"points": "grid", "levels": [3, 3, 3]},
    "kernel": {"kind": "hamming", "length_scale": 0.8, "variance": 0.04},
    "cost": {"kind": "lognormal", "sigma": 0.5},
}


def _flat(trace):
    out = []
    for ev in trace:
        row = [type(ev).__name__, ev.at, ev.tenant_key]
        if hasattr(ev, "K_block"):
            row += [np.asarray(ev.K_block).tobytes(), ev.mu0.tobytes(),
                    ev.cost.tobytes(), ev.z_true.tobytes()]
        out.append(tuple(row))
    return out


def test_churn_copy_matches_the_program_generator():
    from repro.stream import poisson_churn_trace
    seed = 2**33 + 11
    ours = make_trace(CHURN, seed)
    theirs = poisson_churn_trace(num_sessions=60, arrival_rate=2.0,
                                 seed=seed, session_scale=10.0, m_min=2,
                                 m_max=16)
    assert _flat(ours) == _flat(theirs)


def test_same_seed_same_trace_other_seed_other_trace():
    a, b, c = (make_trace(CUBE, s) for s in (7, 7, 8))
    assert _flat(a) == _flat(b) and _flat(a) != _flat(c)


def test_cube_tenants_are_static_with_their_own_psd_blocks():
    trace = make_trace(CUBE, 3)
    evs = list(trace)
    assert [type(e).__name__ for e in evs] == ["TenantArrive"] * 3
    assert all(e.at == 0.0 and e.num_models == 50 for e in evs)
    assert not np.array_equal(evs[0].K_block, evs[1].K_block)
    for e in evs:
        K = np.asarray(e.K_block, np.float64)
        assert np.allclose(K, K.T, atol=1e-6)
        assert np.linalg.eigvalsh(K).min() > -1e-5
        assert e.z_true.min() == 0.0 and (e.cost > 0).all()


def test_mean_cost():
    assert mean_cost({"kind": "uniform"}) == 1.0
    assert np.isclose(mean_cost({"kind": "lognormal", "sigma": 0.5}),
                      np.exp(0.125))


def test_a_structure_seed_fixes_the_schedule_and_the_seed_the_data():
    spec = {"structure_seed": 1, **CHURN}
    a, b = make_trace(spec, 5), make_trace(spec, 6)
    sched = [[(type(e).__name__, e.at, e.tenant_key,
               getattr(e, "num_models", None)) for e in t] for t in (a, b)]
    assert sched[0] == sched[1]
    za = [e.z_true.tobytes() for e in a if hasattr(e, "z_true")]
    zb = [e.z_true.tobytes() for e in b if hasattr(e, "z_true")]
    assert za != zb


def test_grid_block_is_the_hamming_kernel_pair_by_pair():
    ell, var = GRID["kernel"]["length_scale"], GRID["kernel"]["variance"]
    K = next(iter(make_trace(GRID, 1))).K_block
    points = list(itertools.product(range(3), repeat=3))   # C order
    want = np.array([[var * np.exp(-sum(x != y for x, y in zip(a, b)) / ell)
                      for b in points] for a in points])
    assert K.dtype == np.float64 and K.shape == (27, 27)
    np.testing.assert_allclose(K, want, rtol=0, atol=1e-12)
    rho = np.exp(-1.0 / ell)
    assert np.isclose(np.linalg.eigvalsh(K).min(), var * (1 - rho) ** 3,
                      rtol=1e-9)


def test_the_kronecker_of_the_cholesky_factors_is_a_factor_of_the_block():
    Cs, Ls = hamming_factors([3, 2, 4], 1.0, 0.04)
    K, L = functools.reduce(np.kron, Cs), functools.reduce(np.kron, Ls)
    np.testing.assert_allclose(L @ L.T, K, rtol=0, atol=1e-12)
    x = np.random.default_rng(0).standard_normal(24)
    np.testing.assert_allclose(kron_apply(Ls, x), L @ x, rtol=0,
                               atol=1e-12)


def test_grid_seed_fixes_the_trace_and_every_tenant_shares_one_block():
    a, b, c = (list(make_trace(GRID, s)) for s in (2**33 + 5, 2**33 + 5,
                                                    2**33 + 6))
    assert _flat(a) == _flat(b)
    assert [e.z_true.tobytes() for e in a] != [e.z_true.tobytes() for e in c]
    assert all(e.K_block is a[0].K_block for e in a)
    assert len({e.z_true.tobytes() for e in a}) == 3
    for e in a:
        assert e.at == 0.0 and e.num_models == 27
        assert e.z_true.min() == 0.0 and (e.cost > 0).all()


@pytest.mark.parametrize("space, kernel, match", [
    ({"dims": 7, "points": "uniform"}, GRID["kernel"], "no kernel"),
    (GRID["space"], CUBE["kernel"], "no kernel"),
    ({"points": "grid", "levels": [3, 3]}, GRID["kernel"],
     "candidate count"),
], ids=["hamming-on-uniform", "matern52-on-grid", "grid-of-other-size"])
def test_a_kernel_off_its_space_or_a_grid_of_another_size_is_an_error(
        space, kernel, match):
    with pytest.raises(ValueError, match=match):
        make_trace({**GRID, "space": space, "kernel": kernel}, 1)
