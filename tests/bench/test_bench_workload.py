"""The benchmark's own traffic generator."""

import numpy as np

import tinybench  # noqa: F401  (puts the checkout on sys.path)
from bench.workload import make_trace, mean_cost

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
