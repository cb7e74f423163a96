"""The block engine's device-resident posterior pool.

The device scorers read the pool's mean, variance and sd, which a fold
updates in place with one dispatch per dirty block; the sharded scorer and
checkpoints read the host cache, which the host flush reads back.  Both
must hold the same numbers, bit for bit, through admissions, departures,
relocation, capacity growth and a checkpoint round trip."""

import numpy as np
import pytest

from repro.core import ControlPlane
from repro.core.fleet import Fleet
from repro.core.gp import BlockIncrementalGP
from repro.core.tenancy import _matern_block_chol
from repro.stream import StreamEngine, poisson_churn_trace


def _block(m: int, seed: int):
    """(K, mu0, cost) of one tenant's m-point Matérn prior."""
    K, _ = _matern_block_chol(m, 0.3, 0.04)
    rng = np.random.default_rng(seed)
    return K, rng.normal(0.0, 0.05, m), rng.uniform(1.0, 2.0, m)


def _plane():
    return ControlPlane(np.random.default_rng(0), scorer="fused",
                        num_shards=4, model_capacity=16, tenant_capacity=2)


class _Twins:
    """Two planes fed the same steps: ``dev`` decides on its device pool
    and never flushes to the host; ``host`` only ever flushes to the host,
    at the points where ``dev`` decides."""

    def __init__(self):
        self.dev, self.host = _plane(), _plane()
        self.rng = np.random.default_rng(7)
        self.handles = []
        self.pending = []           # picks launched, not yet observed
        self.decisions = 0

    def both(self, fn):
        outs = [fn(cp) for cp in (self.dev, self.host)]
        return outs[0]

    def admit(self, m: int):
        seed = len(self.handles)
        self.handles.append(self.both(
            lambda cp: cp.add_tenant(*_block(m, seed))))

    def retire(self, i: int):
        h = self.handles.pop(i)
        self.both(lambda cp: cp.retire_tenant(h.tenant_id))

    def compact(self):
        remap = self.both(lambda cp: cp.compact(1.0))
        for i, h in enumerate(self.handles):
            if h.tenant_id in remap:
                self.handles[i] = type(h)(h.tenant_id, remap[h.tenant_id][1])
        return remap

    def observe(self, g: int):
        z = float(self.rng.uniform(0.0, 0.3))
        self.both(lambda cp: cp.record_observation(g, z))

    def fold(self, blocks: int):
        """The launched picks' observations, then one into each of
        ``blocks`` distinct tenants."""
        while self.pending:
            self.observe(self.pending.pop())
        for i in self.rng.permutation(len(self.handles))[:blocks]:
            free = [int(g) for g in self.handles[i].models
                    if not self.dev.selected[g]]
            if free:
                self.both(lambda cp: cp.record_start(free[0]))
                self.observe(free[0])

    def round_trip(self):
        """Both planes through ``state_snapshot``/``load_state``, onto
        fresh planes of the same configuration."""
        def restored(cp):
            fresh = _plane()
            fresh.load_state(*cp.state_snapshot())
            return fresh
        self.dev, self.host = restored(self.dev), restored(self.host)

    def decide(self):
        pick = self.dev.choose_mdmt()
        mu, var, sd = self.dev.gp.flush_device()
        mu_h, var_h = self.host.gp.posterior_host()
        # entries of retired blocks are masked and keep whichever value
        # each cache last had; every live entry must agree exactly
        live = self.dev.model_live
        np.testing.assert_array_equal(live, self.host.model_live)
        assert mu.dtype == var.dtype == sd.dtype == np.float32
        np.testing.assert_array_equal(np.asarray(mu)[live], mu_h[live])
        np.testing.assert_array_equal(np.asarray(var)[live], var_h[live])
        np.testing.assert_array_equal(np.asarray(sd)[live],
                                      np.sqrt(var_h)[live])
        self.decisions += 1
        if pick is not None:
            self.both(lambda cp: cp.record_start(pick[0]))
            self.pending.append(pick[0])


def test_device_pool_equals_the_host_flush_through_churn():
    tw = _Twins()
    for m in (3, 2, 4):
        tw.admit(m)
    tw.decide()                             # first upload, nothing dirty
    for _ in range(3):
        tw.fold(2)                          # several dirty blocks
        tw.decide()
    tw.admit(6)                             # capacity grows (16 -> 32)
    assert tw.dev.capacity == 32
    tw.fold(3)
    tw.decide()
    tw.fold(1)
    tw.retire(0)                            # a dirty block departs
    tw.decide()
    tw.admit(3)                             # reuses freed slots
    tw.admit(2)
    tw.fold(2)
    tw.decide()
    tw.retire(1)
    tw.retire(1)
    tw.fold(2)                              # nothing in flight, blocks dirty
    assert tw.compact(), "no block moved"
    tw.decide()
    tw.fold(2)
    tw.round_trip()                         # restored with dirty blocks
    tw.decide()
    for _ in range(3):
        tw.fold(2)
        tw.decide()
    # the deciding plane never read a block back: its host cache lags
    assert tw.decisions == 12 and tw.dev.gp._dirty


@pytest.mark.parametrize("change", ["grow", "restore"])
def test_a_layout_change_replaces_the_device_pool(change):
    """Growth and a restored host cache each drop a pool already on the
    device: the next read has the new shape or the restored numbers, and
    a block folded since is read out on top."""
    gp = BlockIncrementalGP.empty()
    K, mu0, _ = _block(3, 0)
    gp.add_block(np.arange(3), K, mu0)
    gp.ensure_capacity(8)
    gp.observe(1, 0.2)
    gp.posterior()                          # the pool is on the device
    gp.observe(2, 0.1)
    mu_h, var_h = (a.copy() for a in gp.posterior_host())
    if change == "grow":
        gp.ensure_capacity(12)
        mu_h, var_h = (np.concatenate([a, np.zeros(4, np.float32)])
                       for a in (mu_h, var_h))
    else:
        mu_h[5:], var_h[5:] = 1.5, 0.25     # slots of no block
        gp.restore_cache(mu_h, var_h, dirty=[])
    gp.observe(0, 0.3)
    mu, var = map(np.asarray, gp.posterior())
    mu_b, var_b = map(np.asarray, gp._engines[0].posterior())
    mu_h[:3], var_h[:3] = mu_b, var_b
    np.testing.assert_array_equal(mu, mu_h)
    np.testing.assert_array_equal(var, var_h)


@pytest.mark.parametrize("seed", [5, 6])
def test_fused_and_sharded_scorers_pick_the_same_models(seed):
    """A short churn trace: the fused scorer on the device pool and the
    sharded scorer on the host cache launch the same sequence."""
    trace = poisson_churn_trace(num_sessions=8, arrival_rate=1.0, seed=seed,
                                m_min=2, m_max=8, session_scale=15.0)
    seqs = {}
    for scorer in ("fused", "sharded"):
        res = StreamEngine(Fleet.partition_pod(16, 2), "mdmt", seed=0,
                           scorer=scorer, num_shards=1).run(trace)
        seqs[scorer] = [(t.tenant_key, t.local_model, t.device,
                         round(t.start, 9), t.z) for t in res.trials]
    assert len(seqs["fused"]) > 20
    assert seqs["fused"] == seqs["sharded"]
