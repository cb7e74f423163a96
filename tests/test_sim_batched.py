"""Batched synchronous-slot engine vs the event-driven simulator (DESIGN.md §6)."""

import numpy as np
import pytest

from repro.core import (
    EpisodeSpec,
    regret_curves,
    simulate,
    simulate_batch,
    synthetic_matern_problem,
)


@pytest.fixture(scope="module")
def problem():
    # 3 tenants x 8 models: the acceptance problem, small enough that every
    # test shares one jit entry per (shape) signature.
    return synthetic_matern_problem(num_users=3, num_models_per_user=8, seed=5)


def event_sequence(res):
    return [(t.model, t.user_hint, t.device) for t in res.trials]


def batched_sequence(batch, i):
    n = batch.problem.num_models
    return [(int(batch.trial_model[i, j]), int(batch.trial_user[i, j]),
             int(batch.trial_device[i, j])) for j in range(n)]


def assert_episode_matches(problem, batch, i, res):
    """Trial-for-trial equality: models/devices/hints exact, times close."""
    assert batched_sequence(batch, i) == event_sequence(res)
    np.testing.assert_allclose(
        batch.trial_start[i], [t.start for t in res.trials], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        batch.trial_end[i], [t.end for t in res.trials], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        batch.trial_z[i], [t.z for t in res.trials], rtol=1e-6)


@pytest.mark.parametrize("policy", ["mdmt", "round_robin"])
def test_matches_event_engine(problem, policy):
    """The acceptance gate: identical seed => identical trial sequence."""
    res = simulate(problem, policy, num_devices=2, seed=0)
    batch = simulate_batch(problem, [EpisodeSpec(policy, 2, 0)])
    assert_episode_matches(problem, batch, 0, res)


def test_matches_event_engine_no_warm_start(problem):
    """Algorithm 1 line 1-2 initialization (prior-mean argmax per tenant)."""
    res = simulate(problem, "mdmt", num_devices=2, seed=0, warm_start=0)
    batch = simulate_batch(problem, [EpisodeSpec("mdmt", 2, 0)], warm_start=0)
    assert_episode_matches(problem, batch, 0, res)


def test_heterogeneous_device_speeds(problem):
    """Device-aware EIrate: durations scale by speed, sequence still matches."""
    speeds = (1.0, 4.0)
    res = simulate(problem, "mdmt", num_devices=2, seed=3,
                   device_speeds=np.asarray(speeds))
    batch = simulate_batch(
        problem, [EpisodeSpec("mdmt", 2, 3, device_speeds=speeds)])
    assert_episode_matches(problem, batch, 0, res)
    # the fast device does more of the work
    per_dev = np.bincount(batch.trial_device[0], minlength=2)
    assert per_dev[1] > per_dev[0]


def test_matches_event_engine_at_fig5_shape():
    """The paper's Fig-5 shape (50 tenants x 50 candidates) in a batch of
    several episodes: deep enough that the incremental Cholesky amplifies
    any summation-order difference between the jitted fold and its
    vmapped twin into a flipped decision (the engines split at trial 450
    before the folds fixed their order)."""
    problem = synthetic_matern_problem(num_users=50, num_models_per_user=50,
                                       seed=0)
    res = simulate(problem, "mdmt", num_devices=4, seed=0)
    batch = simulate_batch(problem, [EpisodeSpec("mdmt", M, 0)
                                     for M in (1, 4, 16)])
    assert batched_sequence(batch, 1) == event_sequence(res)


def test_vmap_batch_matches_singleton_runs(problem):
    """vmap over episodes == python loop of single-episode batches."""
    specs = [
        EpisodeSpec("mdmt", 2, 0),
        EpisodeSpec("round_robin", 2, 1),
        EpisodeSpec("random", 2, 2),
        EpisodeSpec("mdmt", 1, 3),
    ]
    batch = simulate_batch(problem, specs)
    for i, spec in enumerate(specs):
        # pad with a throwaway episode so Mmax (a static shape) is unchanged
        single = simulate_batch(problem, [spec, EpisodeSpec("mdmt", 2, 99)])
        assert batched_sequence(batch, i) == batched_sequence(single, 0)
        np.testing.assert_array_equal(batch.trial_start[i], single.trial_start[0])
        np.testing.assert_array_equal(batch.trial_end[i], single.trial_end[0])


@pytest.mark.parametrize("policy", ["mdmt", "round_robin", "random"])
def test_every_model_observed_exactly_once(problem, policy):
    batch = simulate_batch(problem, [EpisodeSpec(policy, 2, 0)])
    assert sorted(batch.trial_model[0].tolist()) == list(range(problem.num_models))


def test_regret_curves_match_host_metrics(problem):
    """In-scan regret integration vs the exact host-side regret.py curves."""
    specs = [EpisodeSpec("mdmt", 2, 0), EpisodeSpec("round_robin", 2, 1)]
    batch = simulate_batch(problem, specs)
    for i in range(len(specs)):
        curves = regret_curves(batch.episode_result(i))
        mask = batch.obs_model[i] >= 0
        times = batch.obs_time[i][mask]
        np.testing.assert_allclose(times, curves.times[1:], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            batch.cum_regret[i][mask], curves.cumulative[1:],
            rtol=1e-3, atol=1e-2)
        # Simultaneous finishes are folded in launch order by the scan but in
        # model-index order by regret.py, so the instantaneous trace is only
        # comparable at tie-group boundaries (where both orders have absorbed
        # the same observation set).
        last_of_time = np.r_[np.diff(times) > 1e-9, True]
        np.testing.assert_allclose(
            batch.inst_regret[i][mask][last_of_time],
            curves.instantaneous[1:][last_of_time],
            rtol=1e-4, atol=1e-5)


def test_instantaneous_regret_monotone(problem):
    """Best-so-far only improves, so the mean per-user gap never rises."""
    batch = simulate_batch(
        problem, [EpisodeSpec(p, 2, s) for s in range(2)
                  for p in ("mdmt", "round_robin", "random")])
    for i in range(batch.num_episodes):
        inst = batch.inst_regret[i][batch.obs_model[i] >= 0]
        assert (np.diff(inst) <= 1e-6).all()


def test_per_episode_z_true_override(problem):
    """Many-seed mode: fresh GP sample per episode, shared prior."""
    other = synthetic_matern_problem(num_users=3, num_models_per_user=8, seed=9)
    batch = simulate_batch(problem, [
        EpisodeSpec("mdmt", 2, 0),
        EpisodeSpec("mdmt", 2, 0, z_true=other.z_true),
    ])
    # episode 1 must behave as if the problem had `other`'s ground truth
    res = simulate(other, "mdmt", num_devices=2, seed=0)
    assert batched_sequence(batch, 1) == event_sequence(res)
    # and the two episodes genuinely differ
    assert batched_sequence(batch, 0) != batched_sequence(batch, 1)


def test_episode_result_respects_z_override(problem):
    """regret.py metrics on an overridden episode must use the override's
    ground truth (z_star/worst), not the shared problem's."""
    other = synthetic_matern_problem(num_users=3, num_models_per_user=8, seed=9)
    batch = simulate_batch(
        problem, [EpisodeSpec("mdmt", 2, 0, z_true=other.z_true)])
    res = batch.episode_result(0)
    np.testing.assert_array_equal(res.problem.z_true, other.z_true)
    curves = regret_curves(res)
    ref = regret_curves(simulate(other, "mdmt", num_devices=2, seed=0))
    np.testing.assert_allclose(curves.cumulative, ref.cumulative, rtol=1e-5)
    # trial z values round-trip through float32, so allow f32-level slack
    assert (curves.instantaneous >= -1e-6).all()


def test_synthetic_matern_z_matches_problem():
    """The cheap many-seed sampler must replay the full generator's draw."""
    from repro.core import synthetic_matern_z
    full = synthetic_matern_problem(num_users=4, num_models_per_user=6, seed=11)
    np.testing.assert_array_equal(
        synthetic_matern_z(num_users=4, num_models_per_user=6, seed=11),
        full.z_true)


def test_rejects_non_block_problems(problem):
    membership = np.ones((2, problem.num_models), dtype=bool)  # overlapping
    bad = type(problem)(
        K=problem.K, mu0=problem.mu0, z_true=problem.z_true,
        cost=problem.cost, membership=membership)
    with pytest.raises(ValueError):
        simulate_batch(bad, [EpisodeSpec("mdmt", 1, 0)])
