"""The per-event decision core shared by every episode engine (Algorithm 1).

``ControlPlane`` owns exactly the state Algorithm 1's loop body needs — the
GP posterior, the selected/observed masks, the per-tenant incumbents — and
exposes it as a stepping API:

  * ``record_start(x)`` / ``record_failure(x)`` / ``record_observation(x, z)``
    fold one scheduler event into the state;
  * ``choose_mdmt`` / ``choose_round_robin`` / ``choose_random`` score the
    unselected pool and return the next launch (the EIrate argmax of eq. 6
    for the paper's policy).

Two construction modes, one implementation:

  * :meth:`ControlPlane.from_problem` — the closed-world mode used by the
    offline simulators (``scheduler.simulate``): every tenant is known up
    front, shapes are exact, behavior is bit-identical to the pre-refactor
    ``_PolicyState``.
  * ``ControlPlane(...)`` with no problem — the open-world mode used by the
    streaming engine (``repro.stream.engine``): tenants arrive and depart at
    runtime via :meth:`add_tenant` / :meth:`retire_tenant`.  Buffers are
    capacity-allocated (doubling growth) so the jitted scoring path keeps a
    stable shape across churn events; a tenant's GP block is appended or
    retired without refactorizing the others (``gp.BlockIncrementalGP``).

Scoring is always the batched multi-tenant EIrate pass over the whole pool:
``scorer="fused"`` (default) is the single-dispatch XLA path
(``ei.choose_next_fused``); ``scorer="ops"`` routes through the
``repro.kernels.ops.eirate`` entry point — the Pallas kernel on TPU, its XLA
reference elsewhere — so the streaming hot loop exercises the same code the
kernel benchmarks measure; ``scorer="sharded"`` partitions the model axis
over a device mesh and runs the decision as one ``shard_map`` program
(``repro.shardgp``, DESIGN.md §10) — decision-equivalent to ``fused``
including tie-breaking, provided both planes use the same ``num_shards``
(the index-space layout is part of the tie-break order).

Index space (dynamic mode): model slots and tenant slots are *recycled* —
``retire_tenant`` returns them to a free pool (``shardgp.layout``) and later
admissions reuse them, so buffers grow with the live-model cap, not with
total models ever admitted.  ``compact()`` additionally relocates idle
tenant blocks between shard spans to keep the sharded scorer's load
imbalance bounded.
"""

from __future__ import annotations

from dataclasses import dataclass
from heapq import heappop, heappush

import jax
import jax.numpy as jnp
import numpy as np

from .ei import (
    choose_next_fused,
    choose_topk_classes,
    eirate_topk_fused,
    single_tenant_ei_scores,
    topk_rows_padded,
)
from .gp import DEFAULT_JITTER, BlockIncrementalGP, make_gp
from .tenancy import Problem
from repro.obs.trace import NULL_TRACER, SCALAR_BYTES

SCORERS = ("fused", "ops", "sharded")

#: candidates kept per forensics record on the fused/ops paths (the
#: sharded path keeps its scorer's own top-k)
FORENSICS_TOPK = 4

#: host scalars one GP fold uploads: the model index (for its kernel row
#: and prior mean, and as an argument), the observed value and the
#: observation count
FOLD_SCALARS = 5

_FLOOR_SDS = 5.0  # "no observation yet" sits this many prior sds below mu0


def _fastest_models(problem: Problem, user: int, count: int) -> list[int]:
    idx = np.nonzero(problem.membership[user])[0]
    order = idx[np.argsort(problem.cost[idx], kind="stable")]
    return list(order[:count])


def no_obs_floor(problem: Problem) -> float:
    """Finite stand-in for "no observation yet": far below any plausible z,
    so unserved tenants dominate the EI sum (see DESIGN.md §7).  Shared by
    all episode engines — the equivalence contract depends on it."""
    prior_sd = float(np.sqrt(np.clip(np.diag(problem.K), 0, None).max()))
    return float(problem.mu0.min()) - _FLOOR_SDS * max(prior_sd, 1e-3)


def warm_start_queue(problem: Problem, warm_start: int) -> list[int]:
    """The initial launch queue: user-major, ``warm_start`` fastest models
    each, deduplicated keeping first occurrence (Section 6.1 protocol).
    ``warm_start=0`` yields Algorithm 1 line 1-2's prior-mean argmax per
    tenant instead.  Shared by all episode engines."""
    pending: list[int] = []
    seen: set[int] = set()
    for u in range(problem.num_users):
        for m in _fastest_models(problem, u, warm_start):
            if m not in seen:
                seen.add(m)
                pending.append(m)
    if warm_start == 0:
        for u in range(problem.num_users):
            idx = np.nonzero(problem.membership[u])[0]
            m = int(idx[np.argmax(problem.mu0[idx])])
            if m not in seen:
                seen.add(m)
                pending.append(m)
    return pending


def tenant_warm_models(cost_block: np.ndarray, mu0_block: np.ndarray,
                       warm_start: int) -> list[int]:
    """Per-tenant warm-start picks (local indices): the ``warm_start``
    cheapest models, or the prior-mean argmax when ``warm_start == 0``.
    Concatenating these tenant-major over disjoint candidate sets reproduces
    :func:`warm_start_queue` exactly — the churn-free equivalence relies on
    it."""
    if warm_start > 0:
        order = np.argsort(np.asarray(cost_block), kind="stable")
        return [int(i) for i in order[:warm_start]]
    return [int(np.argmax(np.asarray(mu0_block)))]


@dataclass(frozen=True)
class TenantHandle:
    """What :meth:`ControlPlane.add_tenant` returns: the tenant's slot and
    the global model ids its block occupies."""
    tenant_id: int
    models: np.ndarray  # (m,) global model indices


class ControlPlane:
    """GP update + EIrate pick, as a reusable stepping API (module docstring)."""

    def __init__(
        self,
        rng: np.random.Generator | None = None,
        *,
        jitter: float = DEFAULT_JITTER,
        scorer: str = "fused",
        model_capacity: int = 64,
        tenant_capacity: int = 8,
        num_shards: int | None = None,
        shard_topk: int = 4,
        score_kernel: str = "xla",
    ):
        if scorer not in SCORERS:
            raise ValueError(f"scorer must be one of {SCORERS}, got {scorer!r}")
        from repro.shardgp import ShardedScorer, ShardLayout
        self.rng = rng or np.random.default_rng(0)
        self.scorer = scorer
        self._jitter = jitter
        self._dynamic = True
        self._num_models = 0        # count of LIVE models
        self._num_tenants = 0       # high-water mark of tenant slots
        self._free_tenant_slots: list[int] = []   # min-heap of retired slots
        self._sharded = (ShardedScorer(num_shards, topk=shard_topk,
                                       kernel=score_kernel)
                         if scorer == "sharded" else None)
        shards = (self._sharded.num_shards if self._sharded is not None
                  else (num_shards or 1))
        cap_n = max(1, model_capacity)
        # every tenant block lives inside one shard span; slot reuse +
        # compaction keep this space O(live cap) under churn (DESIGN.md §10)
        self._layout = ShardLayout(
            num_shards=shards, shard_capacity=-(-cap_n // shards))
        cap_n = self._layout.capacity
        cap_N = max(1, tenant_capacity)
        # padding entries are born selected so every chooser masks them
        self.selected = np.ones(cap_n, dtype=bool)
        self.observed = np.zeros(cap_n, dtype=bool)
        self.cost = np.ones(cap_n, dtype=np.float64)
        self.membership = np.zeros((cap_N, cap_n), dtype=bool)
        self.best = np.full(cap_N, -np.inf)
        self.tenant_live = np.zeros(cap_N, dtype=bool)
        self.model_live = np.zeros(cap_n, dtype=bool)
        self._tenant_floor_stats: dict[int, tuple[float, float]] = {}
        self._block_ids: dict[int, int] = {}
        self._no_obs_floor = 0.0
        self.gp = BlockIncrementalGP.empty(jitter)
        self.gp.ensure_capacity(cap_n)
        self.rr_pointer = 0
        self.tracer = NULL_TRACER
        self._forensics = None
        self._rebuild_mirrors()

    @classmethod
    def from_problem(
        cls,
        problem: Problem,
        rng: np.random.Generator | None = None,
        *,
        jitter: float = DEFAULT_JITTER,
        scorer: str = "fused",
        num_shards: int | None = None,
        shard_topk: int = 4,
        score_kernel: str = "xla",
    ) -> "ControlPlane":
        """Closed-world construction: all tenants at t=0, exact shapes.

        Supports arbitrary (also overlapping) candidate sets — the GP engine
        falls back to the dense incremental factorization when the prior is
        not block-diagonal (``gp.make_gp``).  Churn methods are disabled."""
        n, N = problem.num_models, problem.num_users
        cp = cls.__new__(cls)
        cp.rng = rng or np.random.default_rng(0)
        if scorer not in SCORERS:
            raise ValueError(f"scorer must be one of {SCORERS}, got {scorer!r}")
        cp.scorer = scorer
        cp._jitter = jitter
        cp._dynamic = False
        cp._num_models = n
        cp._num_tenants = N
        cp._free_tenant_slots = []
        cp._layout = None           # closed world: no churn, no reuse
        if scorer == "sharded":
            from repro.shardgp import ShardedScorer
            # pads n to a shard multiple internally
            cp._sharded = ShardedScorer(num_shards, topk=shard_topk,
                                        kernel=score_kernel)
        else:
            cp._sharded = None
        cp.selected = np.zeros(n, dtype=bool)
        cp.observed = np.zeros(n, dtype=bool)
        cp.cost = np.asarray(problem.cost, dtype=np.float64).copy()
        cp.membership = np.asarray(problem.membership, dtype=bool).copy()
        cp.best = np.full(N, -np.inf)
        cp.tenant_live = np.ones(N, dtype=bool)
        cp.model_live = np.ones(n, dtype=bool)
        cp._tenant_floor_stats = {}
        cp._block_ids = {}
        cp._no_obs_floor = no_obs_floor(problem)
        cp.gp = make_gp(problem.K, problem.mu0, problem.membership, jitter)
        cp.rr_pointer = 0
        cp.tracer = NULL_TRACER
        cp._forensics = None
        cp._rebuild_mirrors()
        return cp

    # ---- capacity + device-resident mirrors -------------------------------

    @property
    def num_models(self) -> int:
        """Live models (dynamic mode recycles slots, so this is a count of
        the current pool, not an allocation high-water mark)."""
        return self._num_models

    @property
    def num_tenants(self) -> int:
        return self._num_tenants

    @property
    def capacity(self) -> int:
        return len(self.selected)

    def _rebuild_mirrors(self) -> None:
        """Full host->device refresh; called at construction and on churn
        events (rare relative to decisions, which update incrementally)."""
        tr = self.tracer
        with tr.span("mirrors"):
            cost = self.cost.astype(np.float32)
            best = np.where(np.isfinite(self.best), self.best,
                            self._no_obs_floor).astype(np.float32)
            self._membership_j = jnp.asarray(self.membership)
            self._cost_j = jnp.asarray(cost)
            self._selected_j = jnp.asarray(self.selected)
            self._best_j = jnp.asarray(best)
            if self._sharded is not None:
                self._sharded.refresh(self.membership, self.cost)
            if tr.enabled:
                tr.count("h2d_bytes", self.membership.nbytes + cost.nbytes
                         + self.selected.nbytes + best.nbytes)
                tr.sync((self._membership_j, self._cost_j, self._selected_j,
                         self._best_j))

    def _grow(self, need_models: int, need_tenants: int) -> None:
        cap_n, cap_N = self.capacity, self.membership.shape[0]
        new_n = cap_n
        while new_n < need_models:
            new_n *= 2
        new_N = cap_N
        while new_N < need_tenants:
            new_N *= 2
        if new_n == cap_n and new_N == cap_N:
            return
        pad_n, pad_N = new_n - cap_n, new_N - cap_N
        self.selected = np.concatenate([self.selected, np.ones(pad_n, bool)])
        self.observed = np.concatenate([self.observed, np.zeros(pad_n, bool)])
        self.cost = np.concatenate([self.cost, np.ones(pad_n)])
        self.model_live = np.concatenate([self.model_live, np.zeros(pad_n, bool)])
        grown = np.zeros((new_N, new_n), dtype=bool)
        grown[:cap_N, :cap_n] = self.membership
        self.membership = grown
        self.best = np.concatenate([self.best, np.full(pad_N, -np.inf)])
        self.tenant_live = np.concatenate(
            [self.tenant_live, np.zeros(pad_N, bool)])
        self.gp.ensure_capacity(new_n)

    def _recompute_floor(self) -> None:
        stats = [self._tenant_floor_stats[t]
                 for t in np.nonzero(self.tenant_live)[0]
                 if t in self._tenant_floor_stats]
        if not stats:
            self._no_obs_floor = 0.0
            return
        mu_min = min(s[0] for s in stats)
        sd_max = max(s[1] for s in stats)
        self._no_obs_floor = mu_min - _FLOOR_SDS * max(sd_max, 1e-3)

    # ---- tenant churn ------------------------------------------------------

    def add_tenant(self, K_block, mu0_block, cost_block) -> TenantHandle:
        """Admit one tenant: its GP block, candidate models, and tenant slot
        come from the free pools when churn left any (slot reuse, DESIGN.md
        §10), else extend the space.  O(m) plus a mirror refresh; no other
        tenant's GP state is touched.  The block always lands inside one
        shard span of the layout.  A float64 ``K_block`` that is the same
        array object as a live tenant's passes through as that object, so
        the two share one device prior (``BlockIncrementalGP.add_block``)."""
        if not self._dynamic:
            raise RuntimeError("churn is only supported on dynamic "
                               "ControlPlanes (not from_problem)")
        K_block = np.asarray(K_block, dtype=np.float64)
        mu0_block = np.asarray(mu0_block, dtype=np.float64)
        cost_block = np.asarray(cost_block, dtype=np.float64)
        m = len(mu0_block)
        if K_block.shape != (m, m) or cost_block.shape != (m,):
            raise ValueError("block shapes disagree")
        if (cost_block <= 0).any():
            raise ValueError("costs must be positive")
        tr = self.tracer
        with tr.span("admit", models=m):
            tid = (heappop(self._free_tenant_slots) if self._free_tenant_slots
                   else self._num_tenants)
            start = self._layout.place(tid, m)
            self._grow(self._layout.capacity, tid + 1)
            self._num_tenants = max(self._num_tenants, tid + 1)
            self._num_models += m
            ids = np.arange(start, start + m, dtype=np.int64)
            self._block_ids[tid] = self.gp.add_block(ids, K_block, mu0_block,
                                                     tracer=tr)
            self.selected[ids] = False
            self.observed[ids] = False
            self.cost[ids] = cost_block
            self.model_live[ids] = True
            self.membership[tid, ids] = True
            self.best[tid] = -np.inf
            self.tenant_live[tid] = True
            self._tenant_floor_stats[tid] = (
                float(mu0_block.min()),
                float(np.sqrt(np.clip(np.diag(K_block), 0, None).max())))
            self._recompute_floor()
            self._rebuild_mirrors()
            if tr.enabled:
                # the prior block (kernel, mean, jitter) uploaded at
                # admission, its kernel only where no live block shares it
                # (counted by ``add_block``)
                eng = self.gp._engines[self._block_ids[tid]]
                tr.sync((eng.K, eng.mu0))
        return TenantHandle(tenant_id=tid, models=ids)

    def retire_tenant(self, tenant_id: int) -> None:
        """Depart one tenant: its GP block is freed, its models leave the
        pool (masked selected) and their slots return to the free pool for
        the next admission, its tenant slot likewise.  In-flight models of
        the tenant stay selected — the caller decides whether their
        completions are folded (they cannot be: the block is gone)."""
        if not self._dynamic:
            raise RuntimeError("churn is only supported on dynamic "
                               "ControlPlanes (not from_problem)")
        if not self.tenant_live[tenant_id]:
            raise ValueError(f"tenant {tenant_id} is not live")
        with self.tracer.span("retire"):
            ids = np.nonzero(self.membership[tenant_id])[0]
            self.gp.retire_block(self._block_ids.pop(tenant_id))
            self.membership[tenant_id, :] = False
            self.selected[ids] = True
            self.observed[ids] = False
            self.cost[ids] = 1.0
            self.model_live[ids] = False
            self.tenant_live[tenant_id] = False
            self.best[tenant_id] = -np.inf
            del self._tenant_floor_stats[tenant_id]
            self._layout.release(tenant_id)
            heappush(self._free_tenant_slots, tenant_id)
            self._num_models -= len(ids)
            self._recompute_floor()
            self._rebuild_mirrors()

    def in_flight_mask(self) -> np.ndarray:
        """Models launched but not yet observed (their global ids are baked
        into pending completion events — compaction must not move them)."""
        return self.selected & ~self.observed & self.model_live

    def compact(self, max_imbalance: float | None = None,
                max_moves: int | None = None) -> dict[int, tuple]:
        """Rebalance live tenant blocks across shard spans until the load
        imbalance sits within ``max_imbalance`` (shardgp.compact).  Tenants
        with in-flight trials are pinned.  Returns ``{tenant_id: (old_ids,
        new_ids)}`` so callers holding global model ids (the streaming
        engine's launch queue / ownership maps) can remap.  With one shard
        this is a no-op.

        ``max_moves`` bounds the relocations of one call — the incremental
        mode (DESIGN.md §12): each call does at most that much work (a
        bounded pause) and later calls continue toward the imbalance target,
        amortizing a full stop-the-world pass across many events."""
        if not self._dynamic:
            raise RuntimeError("compaction is only supported on dynamic "
                               "ControlPlanes (not from_problem)")
        from repro.shardgp import compact as _compact
        if max_imbalance is None:
            max_imbalance = _compact.DEFAULT_MAX_IMBALANCE
        in_flight = self.in_flight_mask()
        movable = {
            int(t) for t in np.nonzero(self.tenant_live)[0]
            if not in_flight[self.membership[t]].any()}
        moves = _compact.plan_moves(self._layout, movable, max_imbalance,
                                    max_moves)
        first_old: dict[int, np.ndarray] = {}
        for tid, old_start, new_start in moves:
            m = self._layout.blocks[tid].length
            old_ids = np.arange(old_start, old_start + m, dtype=np.int64)
            new_ids = np.arange(new_start, new_start + m, dtype=np.int64)
            self.gp.relocate_block(self._block_ids[tid], new_ids)
            for arr, fill in ((self.selected, True), (self.observed, False),
                              (self.cost, 1.0), (self.model_live, False)):
                vals = arr[old_ids].copy()
                arr[old_ids] = fill
                arr[new_ids] = vals
            self.membership[tid, old_ids] = False
            self.membership[tid, new_ids] = True
            first_old.setdefault(tid, old_ids)
        if moves:
            self._rebuild_mirrors()
        # compose per-tenant hops: a block can move more than once in one
        # pass, and callers hold the ORIGINAL ids — map them to the final
        # placement, not an intermediate one
        remap: dict[int, tuple] = {}
        for tid, old_ids in first_old.items():
            pl = self._layout.blocks[tid]
            remap[tid] = (old_ids,
                          np.arange(pl.start, pl.stop, dtype=np.int64))
        return remap

    # ---- snapshot / restore (the event-sourced engine, DESIGN.md §12) ------

    def state_snapshot(self) -> tuple[dict, dict]:
        """Full dynamic-mode state as ``(arrays, meta)`` for
        ``checkpoint.store.save_checkpoint``.

        The GP is captured *by construction recipe*, not by weights: per
        live tenant we store its prior block and the block-local observation
        sequence, because ``IncrementalGP``'s jitted append is bit-
        deterministic — replaying the same observations on the same machine
        rebuilds ``W``/``alpha`` exactly.  The float32 readout cache is
        stored verbatim (plus the dirty set), so even entries of retired
        blocks — stale, always masked, but part of byte-level state — are
        restored exactly.  Tenants that share a device prior get one host
        array object, so :meth:`load_state` shares it again."""
        if not self._dynamic:
            raise RuntimeError("state_snapshot is only supported on dynamic "
                               "ControlPlanes (not from_problem)")
        arrays = {
            "cp/selected": self.selected.copy(),
            "cp/observed": self.observed.copy(),
            "cp/cost": self.cost.copy(),
            "cp/membership": self.membership.copy(),
            "cp/best": self.best.copy(),
            "cp/tenant_live": self.tenant_live.copy(),
            "cp/model_live": self.model_live.copy(),
            "cp/gp_mu": self.gp._mu.copy(),
            "cp/gp_var": self.gp._var.copy(),
        }
        bid_to_tid = {bid: tid for tid, bid in self._block_ids.items()}
        priors: dict[int, np.ndarray] = {}  # prior key -> host copy
        for tid, bid in self._block_ids.items():
            eng = self.gp._engines[bid]
            key = self.gp._prior_of[bid]
            if key not in priors:
                priors[key] = np.asarray(eng.K)
            arrays[f"gp/{tid}/K"] = priors[key]
            arrays[f"gp/{tid}/mu0"] = np.asarray(eng.mu0)
            arrays[f"gp/{tid}/obs_idx"] = np.asarray(eng.observed, np.int64)
            arrays[f"gp/{tid}/obs_z"] = np.asarray(
                [eng._z[li] for li in eng.observed], np.float64)
        lay = self._layout
        meta = {
            "num_models": self._num_models,
            "num_tenants": self._num_tenants,
            "free_tenant_slots": list(self._free_tenant_slots),
            "rr_pointer": self.rr_pointer,
            "no_obs_floor": self._no_obs_floor,
            "floor_stats": {str(t): [mn, sd] for t, (mn, sd)
                            in self._tenant_floor_stats.items()},
            "rng_state": self.rng.bit_generator.state,
            "layout": {
                "num_shards": lay.num_shards,
                "shard_capacity": lay.shard_capacity,
                "alloc_capacity": lay.alloc.capacity,
                "free": [[s, l] for s, l in lay.alloc._free],
                "blocks": {str(k): [pl.start, pl.length]
                           for k, pl in lay.blocks.items()},
            },
            "gp_dirty": sorted(bid_to_tid[b] for b in self.gp._dirty),
            "gp_n": self.gp.n,
        }
        return arrays, meta

    def load_state(self, arrays: dict, meta: dict) -> None:
        """Overwrite this (dynamic, same-config) plane with
        :meth:`state_snapshot` output, in place — callers holding references
        (the engine's bound chooser) keep working."""
        from repro.shardgp import ShardLayout
        if not self._dynamic:
            raise RuntimeError("load_state is only supported on dynamic "
                               "ControlPlanes (not from_problem)")
        self.selected = np.array(arrays["cp/selected"], dtype=bool)
        self.observed = np.array(arrays["cp/observed"], dtype=bool)
        self.cost = np.array(arrays["cp/cost"], dtype=np.float64)
        self.membership = np.array(arrays["cp/membership"], dtype=bool)
        self.best = np.array(arrays["cp/best"], dtype=np.float64)
        self.tenant_live = np.array(arrays["cp/tenant_live"], dtype=bool)
        self.model_live = np.array(arrays["cp/model_live"], dtype=bool)
        self._num_models = meta["num_models"]
        self._num_tenants = meta["num_tenants"]
        self._free_tenant_slots = list(meta["free_tenant_slots"])
        self.rr_pointer = meta["rr_pointer"]
        self._no_obs_floor = meta["no_obs_floor"]
        self._tenant_floor_stats = {int(t): (mn, sd) for t, (mn, sd)
                                    in meta["floor_stats"].items()}
        self.rng.bit_generator.state = meta["rng_state"]

        ml = meta["layout"]
        lay = ShardLayout(num_shards=ml["num_shards"], shard_capacity=1)
        lay.shard_capacity = ml["shard_capacity"]
        lay.alloc.capacity = ml["alloc_capacity"]
        lay.alloc._free = [(s, l) for s, l in ml["free"]]
        from repro.shardgp.layout import BlockPlacement
        lay.blocks = {int(k): BlockPlacement(start, length)
                      for k, (start, length) in ml["blocks"].items()}
        self._layout = lay

        self.gp = BlockIncrementalGP.empty(self._jitter)
        self._block_ids = {}
        for k in ml["blocks"]:          # serialized insertion order
            tid = int(k)
            pl = lay.blocks[tid]
            ids = np.arange(pl.start, pl.stop, dtype=np.int64)
            bid = self.gp.add_block(ids, arrays[f"gp/{tid}/K"],
                                    arrays[f"gp/{tid}/mu0"])
            self._block_ids[tid] = bid
            for li, z in zip(arrays[f"gp/{tid}/obs_idx"].tolist(),
                             arrays[f"gp/{tid}/obs_z"].tolist()):
                self.gp.observe(int(ids[li]), float(z))
        self.gp.ensure_capacity(meta["gp_n"])
        # exact cache bytes (incl. stale masked entries of retired blocks),
        # and the dirty set as of the snapshot — the next flush recomputes
        # exactly what the uninterrupted run would have
        self.gp.restore_cache(
            arrays["cp/gp_mu"], arrays["cp/gp_var"],
            [self._block_ids[t] for t in meta["gp_dirty"]])
        self._rebuild_mirrors()

    # ---- mesh shrink / regrow (DESIGN.md §16) ------------------------------

    def reshard(self, num_shards: int) -> dict[int, int]:
        """Re-shard every resident posterior block onto a ``num_shards``
        scoring mesh *through the checkpoint path*: snapshot the full state,
        repartition the layout (``ShardLayout.repartition``), scatter the
        per-slot arrays through the slot remap, and restore via
        :meth:`load_state` — the same recipe crash recovery exercises, so
        no hand-rolled array surgery can drift from it.  The GP is rebuilt
        by replaying each block's local observation sequence (bit-
        deterministic), and retired blocks' stale readout-cache entries are
        dropped (the new mesh starts from deterministic fresh padding).

        When the plane scores sharded, the scorer is rebuilt for the new
        mesh first; at ``num_shards == 1`` it falls back to the fused
        scorer — exact by the fused == sharded decision-equivalence
        contract, so the fallback changes no decision.

        Returns ``{old_global_model_id: new_global_model_id}`` over every
        live block slot (empty = no-op) so the caller can remap its queues,
        ownership maps, and pending completion events."""
        if not self._dynamic:
            raise RuntimeError("reshard is only supported on dynamic "
                               "ControlPlanes (not from_problem)")
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        from repro.shardgp import ShardedScorer, ShardLayout
        if num_shards == self._layout.num_shards:
            return {}
        arrays, meta = self.state_snapshot()
        lay, remap = ShardLayout.repartition(self._layout.blocks, num_shards)
        new_cap = lay.capacity
        cap_N = self.membership.shape[0]
        old = np.fromiter(remap.keys(), np.int64, len(remap))
        new = np.fromiter(remap.values(), np.int64, len(remap))

        def scatter(src, fill, dtype):
            out = np.full(new_cap, fill, dtype=dtype)
            if len(old):
                out[new] = src[old]
            return out

        # padding conventions match construction: born selected, unobserved,
        # unit cost, not live, zeroed readout cache
        arrays["cp/selected"] = scatter(arrays["cp/selected"], True, bool)
        arrays["cp/observed"] = scatter(arrays["cp/observed"], False, bool)
        arrays["cp/cost"] = scatter(arrays["cp/cost"], 1.0, np.float64)
        arrays["cp/model_live"] = scatter(arrays["cp/model_live"], False,
                                          bool)
        arrays["cp/gp_mu"] = scatter(arrays["cp/gp_mu"], 0.0, np.float32)
        arrays["cp/gp_var"] = scatter(arrays["cp/gp_var"], 0.0, np.float32)
        mem = np.zeros((cap_N, new_cap), dtype=bool)
        if len(old):
            mem[:, new] = arrays["cp/membership"][:, old]
        arrays["cp/membership"] = mem
        # preserve registry insertion order — load_state rebuilds the GP in
        # this order, and the uninterrupted-vs-restart equivalence needs it
        meta["layout"] = {
            "num_shards": lay.num_shards,
            "shard_capacity": lay.shard_capacity,
            "alloc_capacity": lay.alloc.capacity,
            "free": [[s, l] for s, l in lay.alloc._free],
            "blocks": {str(k): [pl.start, pl.length]
                       for k, pl in lay.blocks.items()},
        }
        meta["gp_n"] = new_cap
        if self.scorer == "sharded":
            if num_shards == 1:
                self.scorer = "fused"
                self._sharded = None
            elif num_shards != self._sharded.num_shards:
                self._sharded = ShardedScorer(
                    num_shards, topk=self._sharded.topk,
                    kernel=self._sharded.kernel)
        self.load_state(arrays, meta)
        return remap

    # ---- observability (DESIGN.md §13) -------------------------------------

    def set_tracer(self, tracer) -> None:
        """Install a ``repro.obs.Tracer`` on the decision path (and on the
        sharded scorer, which opens its own pad/dispatch spans).  Tracing is
        observation-only: spans never change a decision and never enter
        :meth:`state_snapshot`."""
        self.tracer = tracer
        if self._sharded is not None:
            self._sharded.tracer = tracer

    def capacity_stats(self) -> dict:
        """Host-side resource accounting of the posterior + index space —
        the capacity plane's one-stop introspection point
        (``obs/accounting.py``).  GP stats come from
        :meth:`BlockIncrementalGP.resource_stats` keyed back to *tenant*
        slots (block ids are internal); layout occupancy is per shard span.
        Closed-world instances (``from_problem``) have no layout and a
        possibly non-block GP — both degrade to None rather than faking
        numbers.  No device syncs anywhere on this path."""
        gp_stats = None
        if hasattr(self.gp, "resource_stats"):
            gp_stats = self.gp.resource_stats()
            if "blocks" in gp_stats:
                bid_to_tid = {bid: tid for tid, bid in self._block_ids.items()}
                # closed-world blocks (from_problem) have no tenant slot
                # mapping — fall back to the block id itself
                gp_stats["tenants"] = {
                    bid_to_tid.get(bid, bid): stats
                    for bid, stats in gp_stats.pop("blocks").items()}
        layout = (self._layout.occupancy()
                  if self._layout is not None else None)
        return {"gp": gp_stats, "layout": layout}

    def set_forensics(self, recorder) -> None:
        """Install a ``repro.obs.ForensicsRecorder`` on the decision path.
        Observation-only: when enabled, the sharded path keeps the top-k the
        decision already materializes (``decide()`` is the head of
        ``decide_topk()``) and the fused/ops paths run one *additional*
        jitted top-k program — the decision itself is computed by the same
        program either way."""
        self._forensics = recorder

    def _base_cost(self, g: int) -> float:
        """Host-side cost of one candidate, valid across the sharded
        scorer's padded capacity (padding cost is 1.0 by convention)."""
        if self._sharded is not None and self._sharded._cost_host is not None:
            ch = self._sharded._cost_host
            if g < len(ch):
                return float(ch[g])
        return float(self.cost[g]) if g < len(self.cost) else 1.0

    def _record_forensics(self, values, gids, mu, sd, *,
                          speed: float = 1.0, overhead: float = 0.0,
                          device_class: str | None = None) -> None:
        """Feed one materialized top-k into the forensics recorder, with the
        host-side μ/σ/cost decomposition aligned to the candidates."""
        values = np.asarray(values)
        gids = np.asarray(gids)
        mu = np.asarray(mu)
        sd = np.asarray(sd)
        n = mu.shape[0]
        eff, mu_k, sd_k = [], [], []
        for gi in gids:
            gi = int(gi)
            eff.append(self._base_cost(gi) / speed + overhead)
            mu_k.append(float(mu[gi]) if gi < n else 0.0)
            sd_k.append(float(sd[gi]) if gi < n else 0.0)
        self._forensics.on_decision(
            scorer=self.scorer, values=values, gids=gids, eff_costs=eff,
            mu=mu_k, sd=sd_k, speed=speed, device_class=device_class)

    def _record_batch_forensics(self, v, g, mu, sd, rates, overheads,
                                class_names) -> None:
        """One forensics record per class row of a batched decision (the
        (C, k) top-k the greedy assignment consumes)."""
        if self._forensics is None:
            return
        rates = np.asarray(rates, dtype=np.float64)
        overheads = np.asarray(overheads, dtype=np.float64)
        for c in range(v.shape[0]):
            name = (str(class_names[c]) if class_names is not None
                    else f"class{c}")
            self._record_forensics(v[c], g[c], mu, sd,
                                   speed=float(rates[c]),
                                   overhead=float(overheads[c]),
                                   device_class=name)

    # ---- event steps -------------------------------------------------------

    def best_effective(self) -> np.ndarray:
        return np.where(np.isfinite(self.best), self.best, self._no_obs_floor)

    def _count_scalar_upload(self, scalars: int) -> None:
        if self.tracer.enabled:
            self.tracer.count("h2d_bytes", scalars * SCALAR_BYTES)

    def record_start(self, model: int) -> None:
        self.selected[model] = True
        self._selected_j = self._selected_j.at[model].set(True)
        self._count_scalar_upload(2)        # the index and the flag

    def record_failure(self, model: int) -> None:
        # Paper's abstraction makes failure handling trivial: the model was
        # never observed, so it simply returns to L \ L(t).
        self.selected[model] = False
        self._selected_j = self._selected_j.at[model].set(False)
        self._count_scalar_upload(2)

    def record_observation(self, model: int, z: float) -> bool:
        """Fold one observation; returns True when it improved at least one
        member tenant's incumbent (the health plane's regret-stall signal —
        callers that predate the health plane ignore the return).

        Non-finite ``z`` is rejected loudly (DESIGN.md §16): a NaN here
        corrupts the incremental Cholesky and every later decision.  The
        engines check upstream and route poisoned losses through
        ``record_failure`` instead; this raise is the hard boundary for
        callers that don't."""
        if not np.isfinite(z):
            raise ValueError(f"non-finite observation {z!r} for model "
                             f"{model}; poisoned losses must not reach the "
                             f"GP (use record_failure)")
        self.observed[model] = True
        tr = self.tracer
        with tr.span("gp_fold", model=model):
            self.gp.observe(model, z, tr)
            if tr.enabled:
                tr.count("h2d_bytes", FOLD_SCALARS * SCALAR_BYTES)
                tr.sync(self.gp.fold_outputs(model))
        users = np.nonzero(self.membership[:, model])[0]
        improved = False
        for u in users:
            if z > self.best[u] or not np.isfinite(self.best[u]):
                self.best[u] = max(z, self.best[u]) if np.isfinite(self.best[u]) else z
                self._best_j = self._best_j.at[u].set(self.best[u])
                self._count_scalar_upload(2)    # the index and the value
                improved = True
        return improved

    # ---- policy decisions --------------------------------------------------

    def _posterior_sd(self, *, host: bool):
        """The pool's posterior (mu, sd) under the ``posterior`` span.  On
        the device path, the block engine's device pool after its device
        flush (``gp_flush``: a dispatch for each dirty block, and after a
        layout change a ``posterior_upload`` first); with ``host``, its
        host cache after the host flush (the dirty blocks read back) and a
        host sqrt, for the sharded upload.  The dense engine reads out on
        the device."""
        tr = self.tracer
        with tr.span("posterior", scorer=self.scorer):
            gp = self.gp
            if not isinstance(gp, BlockIncrementalGP):
                return tr.sync(gp.posterior_sd())
            if host:
                gp.flush(tr)
                # float32 sqrt is bit-deterministic, so this matches the
                # device pool's jnp sqrt exactly
                mu, var = gp.posterior_host()
                return mu, np.sqrt(var)
            mu, _, sd = gp.flush_device(tr)
            return tr.sync((mu, sd))

    def _count_readback(self, *arrays) -> None:
        """Count blocking readbacks of device ``arrays`` (those the host
        converts), by their bytes."""
        tr = self.tracer
        if tr.enabled:
            tr.count("host_syncs", len(arrays))
            tr.count("d2h_bytes", sum(a.nbytes for a in arrays))

    def choose_mdmt(self, device_speed: float = 1.0) -> tuple[int, int] | None:
        if self.selected.all():
            return None
        tr = self.tracer
        if self.scorer == "sharded":
            # stay on host buffers until the sharded upload
            mu, sd = self._posterior_sd(host=True)
            with tr.span("score", scorer="sharded"):
                if self._forensics is None:
                    idx, score = self._sharded.decide(
                        mu, sd, self._best_j, self.selected, device_speed)
                else:
                    # decide() is literally the head of decide_topk(), so
                    # keeping the k candidates changes no decision — it
                    # just stops discarding what the program materialized
                    v, g = self._sharded.decide_topk(
                        mu, sd, self._best_j, self.selected, device_speed)
                    idx, score = int(g[0]), float(v[0])
                    self._record_forensics(v, g, mu, sd, speed=device_speed)
            if not np.isfinite(score) or score <= -1e29:
                return None
            return idx, -1
        mu, sd = self._posterior_sd(host=False)
        if device_speed == 1.0:
            cost = self._cost_j
        else:
            cost = self._cost_j / device_speed
            self._count_scalar_upload(1)
        with tr.span("score", scorer=self.scorer):
            if self.scorer == "ops":
                from repro.kernels import ops
                scores = ops.eirate(
                    mu, sd, self._best_j, self._membership_j, cost,
                    self._selected_j,
                    use_pallas=jax.default_backend() == "tpu")
                idx = jnp.argmax(scores)
                self._count_readback(idx)
                idx = int(idx)
                score = scores[idx]
                self._count_scalar_upload(1)    # the index into scores
                self._count_readback(score)
                score = float(score)
            else:
                idx, score = choose_next_fused(
                    mu, sd, self._best_j, self._membership_j, cost,
                    self._selected_j)
                self._count_readback(idx, score)
                idx, score = int(idx), float(score)
        if self._forensics is not None:
            # one additional jitted top-k over the same masked EIrate
            # vector; its head equals the decision's argmax (keep-earlier
            # tie-break), the decision above is untouched
            v, g = eirate_topk_fused(
                mu, sd, self._best_j, self._membership_j, cost,
                self._selected_j, k=FORENSICS_TOPK)
            self._record_forensics(v, g, mu, sd, speed=device_speed)
        if not np.isfinite(score) or score <= -1e29:
            return None
        return idx, -1

    def choose_mdmt_batch(self, rates, overheads, k: int, *,
                          class_names=None) -> tuple[np.ndarray, np.ndarray]:
        """One scoring pass for a k-device joint assignment (DESIGN.md §11).

        ``rates``/``overheads`` carry one entry per *device class* present
        in the batch; class c's cost row is ``cost / rates[c] +
        overheads[c]``.  Returns per-class EIrate top-k over the unselected
        pool as numpy ``(values (C, k), global ids (C, k))`` — the greedy
        device<->model solver (``devplane.assign``) consumes them.  With a
        single class at rate 1 / overhead 0, row 0's head is bit-identical
        to :meth:`choose_mdmt`'s pick (the ``/ 1.0`` and ``+ 0.0`` are IEEE
        identities), which is the batched == sequential contract.

        ``class_names`` (optional, len C) labels the per-class forensics
        records when a recorder is installed; it never affects scoring.
        """
        rates_j = jnp.asarray(np.asarray(rates, np.float32))
        over_j = jnp.asarray(np.asarray(overheads, np.float32))
        tr = self.tracer
        if tr.enabled:
            tr.count("h2d_bytes", rates_j.nbytes + over_j.nbytes)
        if self.selected.all():
            # same early-out as choose_mdmt: an empty pool must not pay a
            # scoring pass (dry passes dominate idle stretches)
            C = rates_j.shape[0]
            return (np.full((C, k), -np.inf, np.float32),
                    np.zeros((C, k), np.int64))
        if self.scorer == "sharded":
            mu, sd = self._posterior_sd(host=True)
            with tr.span("score_topk", scorer="sharded", k=k):
                v, g = self._sharded.decide_topk_classes(
                    mu, sd, self._best_j, self.selected, rates_j, over_j, k=k)
                self._count_readback(v, g)
                v, g = np.asarray(v), np.asarray(g)
                self._record_batch_forensics(v, g, mu, sd, rates, overheads,
                                             class_names)
                return v, g
        mu, sd = self._posterior_sd(host=False)
        cm = self._cost_j[None, :] / rates_j[:, None] + over_j[:, None]
        with tr.span("score_topk", scorer=self.scorer, k=k):
            if self.scorer == "ops":
                from repro.kernels import ops
                scores = ops.eirate_classes(
                    mu, sd, self._best_j, self._membership_j, cm,
                    self._selected_j,
                    use_pallas=jax.default_backend() == "tpu")
                v, i = topk_rows_padded(scores, k)
            else:
                v, i = choose_topk_classes(
                    mu, sd, self._best_j, self._membership_j, cm,
                    self._selected_j, k=k)
            self._count_readback(v, i)
            v, i = np.asarray(v), np.asarray(i)
            self._record_batch_forensics(v, i, mu, sd, rates, overheads,
                                         class_names)
            return v, i

    def _users_with_work(self) -> np.ndarray:
        has_work = (self.membership & ~self.selected[None, :]).any(axis=1)
        return np.nonzero(has_work)[0]

    def _own_gp_ei(self, user: int) -> int | None:
        mu, sd = self.gp.posterior_sd()
        best = self.best[user] if np.isfinite(self.best[user]) else self._no_obs_floor
        scores = single_tenant_ei_scores(
            mu, sd, jnp.asarray(best),
            self._membership_j[user], jnp.asarray(self.selected))
        idx = int(jnp.argmax(scores))
        if not np.isfinite(float(scores[idx])):
            return None
        return idx

    def choose_random(self, device_speed: float = 1.0) -> tuple[int, int] | None:
        users = self._users_with_work()
        if users.size == 0:
            return None
        u = int(self.rng.choice(users))
        m = self._own_gp_ei(u)
        return (m, u) if m is not None else None

    def choose_round_robin(self, device_speed: float = 1.0) -> tuple[int, int] | None:
        users = self._users_with_work()
        if users.size == 0:
            return None
        N = self._num_tenants
        for step in range(N):
            u = (self.rr_pointer + step) % N
            if u in users:
                self.rr_pointer = (u + 1) % N
                m = self._own_gp_ei(u)
                if m is not None:
                    return m, u
        return None

    def chooser(self, policy: str):
        """The decision callable for a policy name (``POLICIES``)."""
        return {
            "mdmt": self.choose_mdmt,
            "random": self.choose_random,
            "round_robin": self.choose_round_robin,
        }[policy]
