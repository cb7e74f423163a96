"""Batched synchronous-slot episode engine: many TSHB episodes in one XLA call.

The event-driven simulator in ``scheduler.py`` runs one episode through a
host-Python heap loop — perfect for exactness, terrible for sweeps.  This
module reformulates Algorithm 1 as a fixed-shape ``lax.scan`` in which one
scan step processes exactly one device *slot* (the next device to free), and
a batch of episodes is a single ``jax.vmap`` over per-episode specs
(seed, policy, device count, device-speed vector, optional per-episode
``z_true``).  Thousands of (policy x N x M x seed) scenarios then run as one
accelerator dispatch instead of an overnight host loop.

Exactness (DESIGN.md §6): for the deterministic policies (``mdmt``,
``round_robin``) the engine replays the event-driven simulator's trial
sequence *exactly* — same models, same devices, same launch order — because
each scan step mirrors one heap pop: the device with the minimal
(finish-time, launch-sequence) key is processed, its observation is folded
into the incremental GP (the same ``_append_step`` recurrence as
``gp.IncrementalGP``, block-local), and the policy's pick is launched.  The
``random`` baseline uses a JAX PRNG stream, so it matches the event engine
in distribution but not per-seed.

Structural requirement: tenant candidate sets must be disjoint, equal-sized
and laid out tenant-major (model ``g`` belongs to tenant ``g // m``), with a
block-diagonal prior ``K`` — exactly the structure every problem generator
in ``tenancy.py`` produces, and the same structure ``gp.BlockIncrementalGP``
exploits.  ``simulate_batch`` raises ``ValueError`` otherwise.

Not supported (use ``scheduler.simulate``): device failures, finite
``horizon``.  Both are control-flow features of the host engine that a
fixed-shape scan would have to over-approximate; see DESIGN.md §6.
"""

from __future__ import annotations

import dataclasses
import functools
import time as _time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .ei import expected_improvement
from .gp import DEFAULT_JITTER, rows_dot
from .scheduler import POLICIES, SimResult, TrialRecord, no_obs_floor, warm_start_queue
from .tenancy import Problem

_IDLE_SEQ = np.iinfo(np.int32).max
_POLICY_ID = {p: i for i, p in enumerate(POLICIES)}  # mdmt=0, rr=1, random=2


@dataclass(frozen=True, eq=False)
class EpisodeSpec:
    """One episode of a batched sweep.

    ``device_speeds`` defaults to all-ones; ``z_true`` (length ``n``)
    overrides the problem's ground truth, which is how many-seed synthetic
    sweeps (fresh GP sample per seed, shared prior) batch into one call.
    (``eq=False``: the ndarray field would make the generated ``__eq__`` /
    ``__hash__`` raise; identity semantics are what callers need anyway.)
    """

    policy: str = "mdmt"
    num_devices: int = 1
    seed: int = 0
    device_speeds: tuple[float, ...] | None = None
    z_true: np.ndarray | None = None

    def __post_init__(self):
        if self.policy not in POLICIES:
            raise ValueError(f"policy must be one of {POLICIES}, got {self.policy!r}")
        if self.num_devices < 1:
            raise ValueError("num_devices must be >= 1")
        if self.device_speeds is not None and len(self.device_speeds) != self.num_devices:
            raise ValueError("device_speeds must have num_devices entries")


@dataclass
class BatchResult:
    """Per-episode trial logs + regret curves for a batch of B episodes.

    Trial arrays are in launch order (the same order ``scheduler.simulate``
    appends trials); step arrays are in event-time order (one row per scan
    step; ``obs_model < 0`` marks steps that observed nothing).
    """

    problem: Problem
    specs: tuple[EpisodeSpec, ...]
    warm_start: int
    # (B, n) launch-ordered trial logs
    trial_model: np.ndarray
    trial_user: np.ndarray      # user hint: -2 warm start, -1 mdmt global, else tenant
    trial_device: np.ndarray
    trial_start: np.ndarray
    trial_end: np.ndarray
    trial_z: np.ndarray
    # (B, T) event-ordered step logs
    obs_model: np.ndarray
    obs_time: np.ndarray
    inst_regret: np.ndarray     # mean per-user gap right after each step
    cum_regret: np.ndarray      # Regret_t at each observation step
    # (B,) accounting
    decisions: np.ndarray
    end_time: np.ndarray
    inst0: np.ndarray = None    # (B,) t=0 mean per-user gap (regret clamp)
    wall_seconds: float = 0.0   # total batch wall clock (incl. compile)

    @property
    def num_episodes(self) -> int:
        return self.trial_model.shape[0]

    def episode_result(self, i: int) -> SimResult:
        """Convert episode ``i`` to a :class:`scheduler.SimResult` so the
        exact host-side metrics in ``regret.py`` apply unchanged.

        When the spec overrides ``z_true``, the returned result carries a
        problem rebuilt around that override, so ``regret.py``'s
        ``z_star``/``worst`` are consistent with the logged observations.
        """
        spec = self.specs[i]
        problem = self.problem
        if spec.z_true is not None:
            problem = dataclasses.replace(
                problem, z_true=np.asarray(spec.z_true, problem.z_true.dtype))
        trials = [
            TrialRecord(
                model=int(self.trial_model[i, j]),
                user_hint=int(self.trial_user[i, j]),
                device=int(self.trial_device[i, j]),
                start=float(self.trial_start[i, j]),
                end=float(self.trial_end[i, j]),
                z=float(self.trial_z[i, j]),
            )
            for j in range(self.trial_model.shape[1])
            if self.trial_model[i, j] >= 0
        ]
        return SimResult(
            problem=problem, policy=spec.policy,
            num_devices=spec.num_devices, trials=trials,
            end_time=float(self.end_time[i]), decisions=int(self.decisions[i]),
            decision_seconds=0.0)

    def time_to_instantaneous(self, threshold: float) -> np.ndarray:
        """(B,) first event time the mean per-user gap drops to <= threshold
        (matches ``RegretCurves.time_to_instantaneous``; inf if never)."""
        B = self.num_episodes
        out = np.full(B, np.inf)
        valid = self.obs_model >= 0
        hit = (self.inst_regret <= threshold) & valid
        for i in range(B):
            idx = np.nonzero(hit[i])[0]
            if idx.size:
                out[i] = float(self.obs_time[i, idx[0]])
        # the t=0 point (pre-observation gap) can already satisfy the bar
        out[self.inst0 <= threshold] = 0.0
        return out


# ---------------------------------------------------------------------------
# host-side structure checks + warm-start queue
# ---------------------------------------------------------------------------

def _block_shape(problem: Problem) -> tuple[int, int]:
    """(N, m) if the problem is tenant-major block structured, else raise."""
    mem = np.asarray(problem.membership, bool)
    N, n = mem.shape
    if (mem.sum(axis=0) != 1).any():
        raise ValueError(
            "simulate_batch requires disjoint tenant candidate sets "
            "(every model owned by exactly one tenant)")
    sizes = mem.sum(axis=1)
    if (sizes != sizes[0]).any():
        raise ValueError("simulate_batch requires equal-sized candidate sets")
    m = int(sizes[0])
    for i in range(N):
        if not mem[i, i * m:(i + 1) * m].all():
            raise ValueError(
                "simulate_batch requires tenant-major model layout "
                "(model g owned by tenant g // m)")
    K = np.asarray(problem.K)
    off = K.copy()
    for i in range(N):
        off[i * m:(i + 1) * m, i * m:(i + 1) * m] = 0.0
    if np.abs(off).max(initial=0.0) != 0.0:
        raise ValueError("simulate_batch requires a block-diagonal prior K")
    return N, m


# ---------------------------------------------------------------------------
# the scan engine
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("N", "m", "Mmax", "T", "warm_len"))
def _run_batch(
    Kb, kdiag_b, mu0_b, cost, pending, floor, jitter,
    policy_id, num_devices, seeds, speeds, z_true_b, z_star_b, worst_b,
    *, N: int, m: int, Mmax: int, T: int, warm_len: int,
):
    """vmap-ed scan over episodes.  Shapes:

      Kb (N, m, m), kdiag_b/mu0_b (N, m), cost (n,), pending (warm_len,)
      policy_id/num_devices/seeds (B,), speeds (B, Mmax)
      z_true_b (B, n), z_star_b/worst_b (B, N)
    """
    n = N * m
    owner = jnp.repeat(jnp.arange(N, dtype=jnp.int32), m)
    mu0 = mu0_b.reshape(n)
    kdiag = kdiag_b.reshape(n)

    def episode(pid, nd, seed, speed, z_true, z_star, worst):
        dev_ids = jnp.arange(Mmax, dtype=jnp.int32)
        alive = dev_ids < nd
        state = dict(
            # device slots: finish time, running model, launch-seq tiebreak
            dev_end=jnp.where(alive, 0.0, jnp.inf).astype(jnp.float32),
            dev_model=jnp.full((Mmax,), -1, jnp.int32),
            # t=0 fill order is the free-stack pop order M-1, M-2, ..., 0
            dev_seq=jnp.where(alive, -1 - dev_ids, _IDLE_SEQ).astype(jnp.int32),
            # incremental GP (block-local _append_step buffers)
            W=jnp.zeros((N, m, m), jnp.float32),
            alpha=jnp.zeros((N, m), jnp.float32),
            diag_acc=jnp.zeros((N, m), jnp.float32),
            kcount=jnp.zeros((N,), jnp.int32),
            postmu=mu0.astype(jnp.float32),
            postvar=jnp.maximum(kdiag, 0.0).astype(jnp.float32),
            # policy state
            selected=jnp.zeros((n,), bool),
            best_raw=jnp.full((N,), -jnp.inf, jnp.float32),
            has_obs=jnp.zeros((N,), bool),
            rr_ptr=jnp.int32(0),
            key=jax.random.PRNGKey(seed),
            pend_ptr=jnp.int32(0),
            # trial log + accounting
            counter=jnp.int32(0),
            decisions=jnp.int32(0),
            tr_model=jnp.full((n,), -1, jnp.int32),
            tr_user=jnp.full((n,), -2, jnp.int32),
            tr_dev=jnp.full((n,), -1, jnp.int32),
            tr_start=jnp.zeros((n,), jnp.float32),
            tr_end=jnp.zeros((n,), jnp.float32),
            # regret integration (regret.py convention: pre-observation best
            # clamped to the worst in-set value)
            best_true=worst.astype(jnp.float32),
            t_prev=jnp.float32(0.0),
            cum=jnp.float32(0.0),
        )

        def step(s, _):
            # -- 1. pop the next event: min (finish time, launch seq) --------
            end = s["dev_end"]
            emin = jnp.min(end)
            active = jnp.isfinite(emin)
            tied = end == emin
            d = jnp.argmin(jnp.where(tied, s["dev_seq"], _IDLE_SEQ))
            t = jnp.where(active, emin, s["t_prev"])
            model = s["dev_model"][d]
            do_obs = active & (model >= 0)
            mi = jnp.maximum(model, 0)          # safe index when idle
            b, li = owner[mi], mi % m
            z = z_true[mi]

            # -- 2. regret integral up to t (integrand constant between obs) -
            gapsum = jnp.sum(z_star - s["best_true"])
            cum = s["cum"] + jnp.where(active, gapsum * (t - s["t_prev"]), 0.0)
            t_prev = jnp.where(active, t, s["t_prev"])

            # -- 3. fold the observation into the block-local incremental GP -
            Wb, ab = s["W"][b], s["alpha"][b]
            k_b = s["kcount"][b]
            K_row = Kb[b, li]
            l = Wb[:, li]
            d2 = K_row[li] + jitter - rows_dot(l, l, k_b)
            dchol = jnp.sqrt(jnp.maximum(d2, jitter))
            w_new = (K_row - rows_dot(l, Wb, k_b)) / dchol
            a_new = (z - mu0_b[b, li] - rows_dot(l, ab, k_b)) / dchol
            Wb2 = jax.lax.dynamic_update_index_in_dim(Wb, w_new, k_b, axis=0)
            ab2 = ab.at[k_b].set(a_new)
            dacc2 = s["diag_acc"][b] + w_new * w_new
            mu_blk = mu0_b[b] + rows_dot(ab2, Wb2, jnp.minimum(k_b + 1, m))
            var_blk = jnp.maximum(kdiag_b[b] - dacc2, 0.0)

            W = s["W"].at[b].set(jnp.where(do_obs, Wb2, Wb))
            alpha = s["alpha"].at[b].set(jnp.where(do_obs, ab2, ab))
            diag_acc = s["diag_acc"].at[b].set(
                jnp.where(do_obs, dacc2, s["diag_acc"][b]))
            kcount = s["kcount"].at[b].set(jnp.where(do_obs, k_b + 1, k_b))
            old_mu = jax.lax.dynamic_slice(s["postmu"], (b * m,), (m,))
            old_var = jax.lax.dynamic_slice(s["postvar"], (b * m,), (m,))
            postmu = jax.lax.dynamic_update_slice(
                s["postmu"], jnp.where(do_obs, mu_blk, old_mu), (b * m,))
            postvar = jax.lax.dynamic_update_slice(
                s["postvar"], jnp.where(do_obs, var_blk, old_var), (b * m,))

            best_raw = s["best_raw"].at[b].set(
                jnp.where(do_obs, jnp.maximum(s["best_raw"][b], z),
                          s["best_raw"][b]))
            has_obs = s["has_obs"].at[b].set(s["has_obs"][b] | do_obs)
            best_true = s["best_true"].at[b].set(
                jnp.where(do_obs, jnp.maximum(s["best_true"][b], z),
                          s["best_true"][b]))
            inst = jnp.sum(z_star - best_true) / N

            # -- 4. decide what to launch on the freed device ----------------
            selected = s["selected"]
            any_left = ~jnp.all(selected)
            if warm_len > 0:
                use_pending = s["pend_ptr"] < warm_len
                pend_model = pending[jnp.minimum(s["pend_ptr"], warm_len - 1)]
            else:
                use_pending = jnp.bool_(False)
                pend_model = jnp.int32(0)

            sd = jnp.sqrt(postvar)
            best_eff = jnp.where(has_obs, best_raw, floor)
            # With disjoint candidate sets the multi-tenant EI sum (eq. 4)
            # degenerates to the owner-tenant EI, so one (n,) pass serves
            # both the global EIrate argmax and the per-tenant baselines.
            ei_all = expected_improvement(postmu, sd, best_eff[owner])
            scores = jnp.where(selected, -jnp.inf, ei_all / cost)
            pick_mdmt = jnp.argmax(scores).astype(jnp.int32)

            has_work = (~selected).reshape(N, m).any(axis=1)
            order = (s["rr_ptr"] + jnp.arange(N, dtype=jnp.int32)) % N
            u_rr = order[jnp.argmax(has_work[order])]
            key, sub = jax.random.split(s["key"])
            logits = jnp.where(has_work, 0.0, -jnp.inf)
            u_rand = jnp.where(
                any_left, jax.random.categorical(sub, logits), 0
            ).astype(jnp.int32)
            u_sel = jnp.where(pid == _POLICY_ID["round_robin"], u_rr, u_rand)
            ei_u = jax.lax.dynamic_slice(ei_all, (u_sel * m,), (m,))
            sel_u = jax.lax.dynamic_slice(selected, (u_sel * m,), (m,))
            pick_st = (u_sel * m +
                       jnp.argmax(jnp.where(~sel_u, ei_u, -jnp.inf))
                       ).astype(jnp.int32)

            is_mdmt = pid == _POLICY_ID["mdmt"]
            pick = jnp.where(is_mdmt, pick_mdmt, pick_st)
            hint = jnp.where(is_mdmt, -1, u_sel)
            model_next = jnp.where(use_pending, pend_model, pick)
            hint = jnp.where(use_pending, -2, hint)
            launch = active & any_left

            # -- 5. launch (or retire the device slot) -----------------------
            dur = cost[model_next] / speed[d]
            dev_end = s["dev_end"].at[d].set(
                jnp.where(launch, t + dur,
                          jnp.where(active, jnp.inf, s["dev_end"][d])))
            dev_model = s["dev_model"].at[d].set(
                jnp.where(active, jnp.where(launch, model_next, -1),
                          s["dev_model"][d]))
            dev_seq = s["dev_seq"].at[d].set(
                jnp.where(launch, s["counter"],
                          jnp.where(active, _IDLE_SEQ, s["dev_seq"][d])))
            selected = selected.at[model_next].set(
                selected[model_next] | launch)
            ci = jnp.minimum(s["counter"], n - 1)
            tr_model = s["tr_model"].at[ci].set(
                jnp.where(launch, model_next, s["tr_model"][ci]))
            tr_user = s["tr_user"].at[ci].set(
                jnp.where(launch, hint, s["tr_user"][ci]))
            tr_dev = s["tr_dev"].at[ci].set(
                jnp.where(launch, d.astype(jnp.int32), s["tr_dev"][ci]))
            tr_start = s["tr_start"].at[ci].set(
                jnp.where(launch, t, s["tr_start"][ci]))
            tr_end = s["tr_end"].at[ci].set(
                jnp.where(launch, t + dur, s["tr_end"][ci]))

            s2 = dict(
                dev_end=dev_end, dev_model=dev_model, dev_seq=dev_seq,
                W=W, alpha=alpha, diag_acc=diag_acc, kcount=kcount,
                postmu=postmu, postvar=postvar,
                selected=selected, best_raw=best_raw, has_obs=has_obs,
                rr_ptr=jnp.where(
                    launch & ~use_pending & (pid == _POLICY_ID["round_robin"]),
                    (u_rr + 1) % N, s["rr_ptr"]),
                key=key,
                pend_ptr=s["pend_ptr"] + (use_pending & launch),
                counter=s["counter"] + launch,
                decisions=s["decisions"] + (active & ~use_pending),
                tr_model=tr_model, tr_user=tr_user, tr_dev=tr_dev,
                tr_start=tr_start, tr_end=tr_end,
                best_true=best_true, t_prev=t_prev, cum=cum,
            )
            emit = dict(
                obs_model=jnp.where(do_obs, model, -1),
                obs_time=t,
                inst=inst,
                cum=cum,
            )
            return s2, emit

        final, steps = jax.lax.scan(step, state, None, length=T)
        return dict(
            trial_model=final["tr_model"], trial_user=final["tr_user"],
            trial_device=final["tr_dev"], trial_start=final["tr_start"],
            trial_end=final["tr_end"],
            obs_model=steps["obs_model"], obs_time=steps["obs_time"],
            inst=steps["inst"], cum=steps["cum"],
            decisions=final["decisions"], end_time=final["t_prev"],
        )

    return jax.vmap(episode)(
        policy_id, num_devices, seeds, speeds, z_true_b, z_star_b, worst_b)


def simulate_batch(
    problem: Problem,
    specs,
    warm_start: int = 2,
    jitter: float = DEFAULT_JITTER,
) -> BatchResult:
    """Run a batch of TSHB episodes as one jitted ``vmap(scan)`` call.

    Args:
      problem: a tenant-major block-structured :class:`Problem` (all three
        generators in ``tenancy.py`` qualify).
      specs: sequence of :class:`EpisodeSpec`.
      warm_start: fastest-models-per-tenant warm start (Section 6.1; same
        semantics as ``scheduler.simulate``, shared by the whole batch).

    Returns:
      :class:`BatchResult` with launch-ordered trial logs, event-ordered
      regret curves, and per-episode accounting.
    """
    specs = tuple(specs)
    if not specs:
        raise ValueError("specs must be non-empty")
    problem.validate()
    N, m = _block_shape(problem)
    n = N * m
    B = len(specs)
    Mmax = max(s.num_devices for s in specs)
    T = n + Mmax

    K = np.asarray(problem.K, np.float32)
    Kb = np.stack([K[i * m:(i + 1) * m, i * m:(i + 1) * m] for i in range(N)])
    kdiag_b = np.stack([np.diag(Kb[i]) for i in range(N)])
    mu0_b = np.asarray(problem.mu0, np.float32).reshape(N, m)
    cost = np.asarray(problem.cost, np.float32)
    pending = np.asarray(warm_start_queue(problem, warm_start), np.int32)
    floor = no_obs_floor(problem)

    policy_id = np.asarray([_POLICY_ID[s.policy] for s in specs], np.int32)
    num_devices = np.asarray([s.num_devices for s in specs], np.int32)
    seeds = np.asarray([s.seed for s in specs], np.uint32)
    speeds = np.ones((B, Mmax), np.float32)
    for i, s in enumerate(specs):
        if s.device_speeds is not None:
            speeds[i, :s.num_devices] = np.asarray(s.device_speeds, np.float32)
    z_true_b = np.stack([
        np.asarray(s.z_true if s.z_true is not None else problem.z_true,
                   np.float32)
        for s in specs])
    if z_true_b.shape != (B, n):
        raise ValueError(f"per-episode z_true must have shape ({n},)")
    mem = np.asarray(problem.membership, bool)
    z_star_b = np.where(mem[None], z_true_b[:, None, :], -np.inf).max(-1)
    worst_b = np.where(mem[None], z_true_b[:, None, :], np.inf).min(-1)

    t0 = _time.perf_counter()
    out = _run_batch(
        jnp.asarray(Kb), jnp.asarray(kdiag_b), jnp.asarray(mu0_b),
        jnp.asarray(cost), jnp.asarray(pending),
        jnp.float32(floor), jnp.float32(jitter),
        jnp.asarray(policy_id), jnp.asarray(num_devices), jnp.asarray(seeds),
        jnp.asarray(speeds), jnp.asarray(z_true_b),
        jnp.asarray(z_star_b, jnp.float32), jnp.asarray(worst_b, jnp.float32),
        N=N, m=m, Mmax=Mmax, T=T, warm_len=int(pending.size))
    out = jax.tree.map(np.asarray, jax.block_until_ready(out))
    wall = _time.perf_counter() - t0

    tm = out["trial_model"]
    z_log = np.where(
        tm >= 0,
        np.take_along_axis(z_true_b, np.maximum(tm, 0), axis=1),
        np.nan)
    return BatchResult(
        problem=problem, specs=specs, warm_start=warm_start,
        trial_model=tm, trial_user=out["trial_user"],
        trial_device=out["trial_device"], trial_start=out["trial_start"],
        trial_end=out["trial_end"], trial_z=z_log,
        obs_model=out["obs_model"], obs_time=out["obs_time"],
        inst_regret=out["inst"], cum_regret=out["cum"],
        decisions=out["decisions"], end_time=out["end_time"],
        inst0=(z_star_b - worst_b).mean(axis=1),
        wall_seconds=wall)
