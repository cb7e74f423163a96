"""The sharded scoring plane: multi-device GP-EI decisions via shard_map.

One decision = GP posterior readout + batched EIrate over every live model +
argmax over the unselected pool.  Single-device, that whole pass competes
with the fleet for one chip; here the *model axis* is partitioned over a
1-D ``("shard",)`` mesh (``repro.launch.mesh.make_scoring_mesh``) and the
decision runs as one ``shard_map`` program:

  1. each shard scores its local slice of the pool — the same math as
     ``ei.choose_next_fused`` (XLA path) or the Pallas kernels
     (``kernels/ops.eirate_topk`` with the block-local top-k epilogue);
  2. each shard reduces its slice to a local top-k (values + global ids);
  3. one small ``all_gather`` of the S*k candidates, then a replicated
     global pick — max value, ties broken by *lowest global id*.

Exactness (DESIGN.md §10): the per-model scores are elementwise in the model
axis, so sharding changes no value; ``lax.top_k`` prefers lower indices on
equal values, and the gathered candidate list is ordered (shard, rank) which
is ascending in global id — so the global pick is bit-identical to
``jnp.argmax`` over the unsharded score vector, including tie-breaking.
The layout half of the contract (both scorers seeing the same index space)
lives in layout.py.

Per-shard state (membership columns, costs) is device-resident and refreshed
only on churn; per-decision inputs (mu, sd, best, selected) stream in each
call.  Shapes are capacity-padded (padding is born selected), so the jitted
program recompiles only when capacity doubles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.ei import NEG_INF, ei_total
from repro.obs.trace import NULL_TRACER, SCALAR_BYTES
from repro.sharding.rules import SCORING_RULES

SCORE_KERNELS = ("xla", "pallas", "pallas_topk")

# PartitionSpecs derived from the logical-axis table (sharding/rules.py),
# not hard-coded mesh axes — the same knob the data plane turns.
P_MODELS = SCORING_RULES.mesh_axes(("models",))
P_TENANTS = SCORING_RULES.mesh_axes(("tenants",))
P_MEMBER = SCORING_RULES.mesh_axes(("tenants", "models"))
P_W = SCORING_RULES.mesh_axes(("obs", "models"))
P_OBS = SCORING_RULES.mesh_axes(("obs",))


def _global_pick(allv: jax.Array, allg: jax.Array, k: int):
    """Top-k of the gathered (S*k,) candidates.  The flat order is
    (shard, rank)-major = ascending global id at equal value, and lax.top_k
    keeps the earlier element on ties, so ties resolve to the lowest global
    id — identical to single-device argmax."""
    v, pos = jax.lax.top_k(allv, k)
    return v, allg[pos]


def _local_topk(scores: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    # a shard's slice can be smaller than k (tiny pool, many shards):
    # lax.top_k demands k <= dimension, so clamp and pad with inert
    # candidates — same convention as the Pallas epilogue's kb guard
    kk = min(k, scores.shape[0])
    v, li = jax.lax.top_k(scores, kk)
    base = jax.lax.axis_index("shard") * scores.shape[0]
    g = base + li.astype(jnp.int32)
    if kk < k:
        v = jnp.concatenate([v, jnp.full(k - kk, NEG_INF, v.dtype)])
        g = jnp.concatenate([g, jnp.zeros(k - kk, jnp.int32)])
    return v, g


def _score_local(mu, sd, best, member, cost, selected, speed, kernel: str, k: int):
    """One shard's slice -> (k,) local best values + global ids."""
    cost = cost / speed
    if kernel == "xla":
        # bit-identical to ei.choose_next_fused on the full vector
        total = ei_total(mu, sd, best, member)
        scores = jnp.where(selected, NEG_INF, total / cost)
        return _local_topk(scores, k)
    from repro.kernels import ops
    if kernel == "pallas_topk":
        v, li = ops.eirate_topk(mu, sd, best, member, cost, selected, k=k)
        base = jax.lax.axis_index("shard") * mu.shape[0]
        return v, base + li.astype(jnp.int32)
    scores = ops.eirate(mu, sd, best, member, cost, selected)
    return _local_topk(scores, k)


@functools.partial(jax.jit, static_argnames=("mesh", "kernel", "k"))
def _decide(mu, sd, best, member, cost, selected, speed, *, mesh, kernel, k):
    # named_scope annotations land in device profiles (TensorBoard/Perfetto)
    # next to the host spans the obs tracer bridges in — same taxonomy as
    # the phase-split programs below (DESIGN.md §13)
    def local(mu, sd, best, member, cost, selected, speed):
        with jax.named_scope("score_topk"):
            v, g = _score_local(mu, sd, best, member, cost, selected, speed,
                                kernel, k)
        with jax.named_scope("all_gather"):
            allv = jax.lax.all_gather(v, "shard").reshape(-1)
            allg = jax.lax.all_gather(g, "shard").reshape(-1)
        return allv, allg
    allv, allg = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P_MODELS, P_MODELS, P_TENANTS, P_MEMBER,
                  P_MODELS, P_MODELS, P()),
        out_specs=(P(None), P(None)),
        check_vma=False,
    )(mu, sd, best, member, cost, selected, speed)
    with jax.named_scope("global_pick"):
        return _global_pick(allv, allg, k)


@functools.partial(jax.jit, static_argnames=("mesh", "kernel", "k"))
def _decide_classes(mu, sd, best, member, cost, selected, rates, overheads,
                    *, mesh, kernel, k):
    """Per-device-class decision in ONE shard_map program: each shard
    computes its tenant-axis EI sum once, fans it out against every class's
    cost row (``cost/rate_c + overhead_c`` — the affine 2-D cost of
    DESIGN.md §11), reduces each class row to a local top-k, and one
    all_gather serves every class's global pick.  With ``overheads == 0``
    and a single class this is bit-identical to :func:`_decide` (the
    ``+ 0.0`` and ``/ 1.0`` are IEEE identities), which is what lets the
    joint batched assignment replay sequential decisions exactly on
    homogeneous fleets."""
    C = rates.shape[0]

    def local(mu, sd, best, member, cost, selected, rates, overheads):
        cm = cost[None, :] / rates[:, None] + overheads[:, None]   # (C, nl)
        if kernel == "xla":
            total = ei_total(mu, sd, best, member)
            scores = jnp.where(selected[None, :], NEG_INF,
                               total[None, :] / cm)
        else:
            from repro.kernels import ops
            scores = ops.eirate_classes(mu, sd, best, member, cm, selected)
        per = [_local_topk(scores[c], k) for c in range(C)]
        v = jnp.stack([p[0] for p in per])       # (C, k)
        g = jnp.stack([p[1] for p in per])
        allv = jax.lax.all_gather(v, "shard")    # (S, C, k)
        allg = jax.lax.all_gather(g, "shard")
        return allv, allg

    allv, allg = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P_MODELS, P_MODELS, P_TENANTS, P_MEMBER,
                  P_MODELS, P_MODELS, P(), P()),
        out_specs=(P(None), P(None)),
        check_vma=False,
    )(mu, sd, best, member, cost, selected, rates, overheads)
    # (S, C, k) -> (C, S*k): per class the flat order stays (shard, rank)-
    # major = ascending global id at equal value, so top_k's keep-earlier
    # tie-break still resolves to the lowest global id
    allv = allv.transpose(1, 0, 2).reshape(C, -1)
    allg = allg.transpose(1, 0, 2).reshape(C, -1)
    v, pos = jax.lax.top_k(allv, k)
    return v, jnp.take_along_axis(allg, pos, axis=1)


@functools.partial(jax.jit, static_argnames=("mesh", "kernel", "k"))
def _readout_decide(W, alpha, mu0, kdiag, best, member, cost, selected, speed,
                    *, mesh, kernel, k):
    """The fully fused pipeline: sharded GP readout -> EIrate -> global
    argmax in one program.  W is (k_obs, n) sharded over columns; each shard
    reads its slice of W exactly once (kernels/gp_readout streaming pass)."""
    use_pallas = kernel != "xla"

    def local(W, alpha, mu0, kdiag, best, member, cost, selected, speed):
        from repro.kernels import ops
        with jax.named_scope("gp_readout"):
            mu, sd = ops.gp_readout(W, alpha, mu0, kdiag, emit_sd=True,
                                    use_pallas=use_pallas)
        with jax.named_scope("score_topk"):
            v, g = _score_local(mu, sd, best, member, cost, selected, speed,
                                kernel, k)
        with jax.named_scope("all_gather"):
            allv = jax.lax.all_gather(v, "shard").reshape(-1)
            allg = jax.lax.all_gather(g, "shard").reshape(-1)
        return allv, allg

    allv, allg = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P_W, P_OBS, P_MODELS, P_MODELS, P_TENANTS,
                  P_MEMBER, P_MODELS, P_MODELS, P()),
        out_specs=(P(None), P(None)),
        check_vma=False,
    )(W, alpha, mu0, kdiag, best, member, cost, selected, speed)
    with jax.named_scope("global_pick"):
        return _global_pick(allv, allg, k)


# ---- phase-split programs (span-level cost attribution) ---------------------
# The SAME pipeline as _readout_decide, cut at its two natural barriers so a
# host span (with block_until_ready) can time each phase separately.  These
# are benchmark-only (benchmarks/decision_trace.py): the engines keep the
# fused program when tracing, so a traced run's decisions stay byte-identical
# to an untraced run's.

@functools.partial(jax.jit, static_argnames=("mesh", "kernel"))
def _readout_phase(W, alpha, mu0, kdiag, *, mesh, kernel):
    """Sharded GP posterior readout only -> (mu, sd), model-sharded."""
    use_pallas = kernel != "xla"

    def local(W, alpha, mu0, kdiag):
        from repro.kernels import ops
        with jax.named_scope("gp_readout"):
            return ops.gp_readout(W, alpha, mu0, kdiag, emit_sd=True,
                                  use_pallas=use_pallas)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P_W, P_OBS, P_MODELS, P_MODELS),
        out_specs=(P_MODELS, P_MODELS),
        check_vma=False,
    )(W, alpha, mu0, kdiag)


@functools.partial(jax.jit, static_argnames=("mesh", "kernel", "k"))
def _local_candidates(mu, sd, best, member, cost, selected, speed,
                      *, mesh, kernel, k):
    """Per-shard score + local top-k, candidates left shard-resident (the
    (S*k,) outputs are sharded; no cross-shard traffic yet)."""

    def local(mu, sd, best, member, cost, selected, speed):
        with jax.named_scope("score_topk"):
            return _score_local(mu, sd, best, member, cost, selected, speed,
                                kernel, k)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P_MODELS, P_MODELS, P_TENANTS, P_MEMBER,
                  P_MODELS, P_MODELS, P()),
        out_specs=(P_MODELS, P_MODELS),
        check_vma=False,
    )(mu, sd, best, member, cost, selected, speed)


@functools.partial(jax.jit, static_argnames=("mesh", "k"))
def _gather_pick(allv, allg, *, mesh, k):
    """Cross-shard all_gather of the S*k candidates + replicated global
    pick — the communication epilogue, isolated."""

    def local(v, g):
        with jax.named_scope("all_gather"):
            av = jax.lax.all_gather(v, "shard").reshape(-1)
            ag = jax.lax.all_gather(g, "shard").reshape(-1)
        with jax.named_scope("global_pick"):
            vv, pos = jax.lax.top_k(av, k)
            return vv, ag[pos]

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P_MODELS, P_MODELS),
        out_specs=(P(None), P(None)),
        check_vma=False,
    )(allv, allg)


class ShardedScorer:
    """Device-resident sharded mirrors + the decision entry points.

    ``num_shards`` must not exceed the jax device count; with one shard the
    program is the single-device fused path plus a trivial reduction (used
    by the tier-1 tests — the multi-shard path needs forced host devices).
    """

    def __init__(self, num_shards: int | None = None, *, topk: int = 4,
                 kernel: str = "xla", mesh=None):
        from repro.launch.mesh import make_scoring_mesh
        if kernel not in SCORE_KERNELS:
            raise ValueError(
                f"kernel must be one of {SCORE_KERNELS}, got {kernel!r}")
        if mesh is None:
            mesh = make_scoring_mesh(num_shards)
        self.mesh = mesh
        self.num_shards = mesh.devices.size
        self.topk = max(1, topk)
        self.kernel = kernel
        self.tracer = NULL_TRACER   # installed by ControlPlane.set_tracer
        self._member = None     # (N_cap, cap) device-resident, P(None, shard)
        self._cost = None       # (cap,) device-resident, P(shard)
        self._cost_host = None  # (cap,) host twin: forensics recovers
        #                         EI = score x cost without a device sync
        self._cap = 0

    # ---- sharded mirrors ---------------------------------------------------

    def _padded_cap(self, n: int) -> int:
        s = self.num_shards
        return ((n + s - 1) // s) * s

    def refresh(self, membership: np.ndarray, cost: np.ndarray) -> None:
        """Full host->device refresh of the churn-rate state (membership
        columns + costs), capacity-padded to a shard multiple."""
        n = cost.shape[0]
        cap = self._padded_cap(n)
        mem = np.zeros((membership.shape[0], cap), dtype=bool)
        mem[:, :n] = membership
        c = np.ones(cap, dtype=np.float32)
        c[:n] = cost
        self._member = jax.device_put(
            mem, NamedSharding(self.mesh, P_MEMBER))
        self._cost = jax.device_put(
            c, NamedSharding(self.mesh, P_MODELS))
        self._cost_host = c
        self._cap = cap
        if self.tracer.enabled:
            self.tracer.count("h2d_bytes", mem.nbytes + c.nbytes)

    def _pad(self, x, fill, dtype):
        x = np.asarray(x)
        if x.shape[0] == self._cap:
            return x.astype(dtype, copy=False)
        out = np.full(self._cap, fill, dtype=dtype)
        out[:x.shape[0]] = x
        return out

    # ---- decisions ---------------------------------------------------------

    def decide_topk(self, mu, sd, best, selected, speed: float = 1.0):
        """(values (k,), global ids (k,)) of the global EIrate top-k."""
        if self._member is None:
            raise RuntimeError("refresh() must run before decide()")
        tr = self.tracer
        with tr.span("pad_upload"):
            mu = self._pad(np.asarray(mu, dtype=np.float32), 0.0, np.float32)
            sd = self._pad(np.asarray(sd, dtype=np.float32), 0.0, np.float32)
            sel = self._pad(np.asarray(selected), True, bool)
        with tr.span("shard_decide", shards=self.num_shards,
                     kernel=self.kernel):
            if tr.enabled:      # the padded pool and the speed
                tr.count("h2d_bytes", mu.nbytes + sd.nbytes + sel.nbytes
                         + SCALAR_BYTES)
            return tr.sync(_decide(
                mu, sd, jnp.asarray(best, dtype=jnp.float32), self._member,
                self._cost, sel, jnp.float32(speed),
                mesh=self.mesh, kernel=self.kernel, k=self.topk))

    def decide(self, mu, sd, best, selected,
               speed: float = 1.0) -> tuple[int, float]:
        """The decision the control plane consumes: global argmax (lowest-id
        tie-break) and its score."""
        v, g = self.decide_topk(mu, sd, best, selected, speed)
        idx, score = g[0], v[0]
        if self.tracer.enabled:
            self.tracer.count("host_syncs", 2)
            self.tracer.count("d2h_bytes", idx.nbytes + score.nbytes)
        return int(idx), float(score)

    def decide_topk_classes(self, mu, sd, best, selected, rates, overheads,
                            k: int | None = None):
        """Per-device-class global EIrate top-k for the joint batched
        assignment: ``(values (C, k), global ids (C, k))``, one row per
        class in ``rates``/``overheads`` (cost row c = cost/rate_c +
        overhead_c).  ``k`` defaults to ``self.topk``; a k-device batch
        passes k = batch size so the greedy solver never runs dry."""
        if self._member is None:
            raise RuntimeError("refresh() must run before decide()")
        k = self.topk if k is None else max(1, k)
        tr = self.tracer
        with tr.span("pad_upload"):
            mu = self._pad(np.asarray(mu, dtype=np.float32), 0.0, np.float32)
            sd = self._pad(np.asarray(sd, dtype=np.float32), 0.0, np.float32)
            sel = self._pad(np.asarray(selected), True, bool)
        with tr.span("shard_decide", shards=self.num_shards,
                     kernel=self.kernel, k=k):
            if tr.enabled:
                tr.count("h2d_bytes", mu.nbytes + sd.nbytes + sel.nbytes)
            return tr.sync(_decide_classes(
                mu, sd, jnp.asarray(best, dtype=jnp.float32), self._member,
                self._cost, sel, jnp.asarray(rates, dtype=jnp.float32),
                jnp.asarray(overheads, dtype=jnp.float32),
                mesh=self.mesh, kernel=self.kernel, k=k))

    def readout_decide_topk(self, W, alpha, mu0, kdiag, best, selected,
                            speed: float = 1.0):
        """Fused readout+score+pick over an explicit (k_obs, n) W buffer —
        the shard_scale benchmark's full-pipeline path.  Shapes must already
        be shard-multiples (pad upstream)."""
        if self._member is None:
            raise RuntimeError("refresh() must run before decide()")
        return _readout_decide(
            W, alpha, mu0, kdiag, jnp.asarray(best, dtype=jnp.float32),
            self._member, self._cost, jnp.asarray(selected),
            jnp.float32(speed), mesh=self.mesh, kernel=self.kernel,
            k=self.topk)

    def readout_decide_topk_phased(self, W, alpha, mu0, kdiag, best,
                                   selected, speed: float = 1.0):
        """The same pipeline as :meth:`readout_decide_topk`, run as three
        separately jitted phases — readout, local score+top-k, cross-shard
        gather+pick — each closed under a ``tracer.span`` with a
        ``block_until_ready`` sync, so the tracer attributes the decision's
        wall time phase by phase.  Benchmark-only: the extra dispatch
        boundaries forfeit fusion, so the engines never take this path."""
        if self._member is None:
            raise RuntimeError("refresh() must run before decide()")
        tr = self.tracer
        best_j = jnp.asarray(best, dtype=jnp.float32)
        sel_j = jnp.asarray(selected)
        speed_j = jnp.float32(speed)
        with tr.span("readout", shards=self.num_shards):
            mu, sd = tr.sync(_readout_phase(
                W, alpha, mu0, kdiag, mesh=self.mesh, kernel=self.kernel))
        with tr.span("score_topk", shards=self.num_shards, k=self.topk):
            v, g = tr.sync(_local_candidates(
                mu, sd, best_j, self._member, self._cost, sel_j, speed_j,
                mesh=self.mesh, kernel=self.kernel, k=self.topk))
        with tr.span("gather_pick", shards=self.num_shards, k=self.topk):
            return tr.sync(_gather_pick(v, g, mesh=self.mesh, k=self.topk))

    def phase_times(self, W, alpha, mu0, kdiag, best, selected,
                    speed: float = 1.0, *, iters: int = 10,
                    warmup: int = 2) -> dict:
        """Mean wall µs per phase of the phased pipeline — the capacity
        plane's attribution probe (obs/profile.py, benchmarks/capacity.py).
        Each phase is timed independently on materialized inputs (the
        chain's intermediates are computed once, outside the timed region),
        so the numbers decompose a decision without dispatch pipelining
        hiding one phase inside another."""
        from repro.obs.profile import time_us_blocked
        if self._member is None:
            raise RuntimeError("refresh() must run before phase_times()")
        best_j = jnp.asarray(best, dtype=jnp.float32)
        sel_j = jnp.asarray(selected)
        speed_j = jnp.float32(speed)
        mu, sd = jax.block_until_ready(_readout_phase(
            W, alpha, mu0, kdiag, mesh=self.mesh, kernel=self.kernel))
        v, g = jax.block_until_ready(_local_candidates(
            mu, sd, best_j, self._member, self._cost, sel_j, speed_j,
            mesh=self.mesh, kernel=self.kernel, k=self.topk))
        return {
            "readout_us": time_us_blocked(
                lambda: _readout_phase(W, alpha, mu0, kdiag, mesh=self.mesh,
                                       kernel=self.kernel),
                iters=iters, warmup=warmup),
            "score_us": time_us_blocked(
                lambda: _local_candidates(
                    mu, sd, best_j, self._member, self._cost, sel_j,
                    speed_j, mesh=self.mesh, kernel=self.kernel,
                    k=self.topk),
                iters=iters, warmup=warmup),
            "gather_us": time_us_blocked(
                lambda: _gather_pick(v, g, mesh=self.mesh, k=self.topk),
                iters=iters, warmup=warmup),
        }
