"""The plain reference: GP-EI decisions in float64, and the control.

It imports nothing of the program.  Given the trace a run was fed (each
tenant's prior block, prior mean, costs and ground truth) and what the run
did (the engine's processed-event log and its trial list), it replays the
run's own history, teacher-forced: every observation the run folded, every
launch it made, in the run's order.  At each policy decision it is asked
to check, it computes from that state, in float64:

* each live tenant's posterior over its candidates, the zero-noise GP of
  the paper conditioned on the tenant's observations (prior block plus
  ``jitter`` on the observed diagonal), kept as an incremental Cholesky
  factor ``W = L^{-1} K[obs, :]`` (Rasmussen & Williams, alg. 2.1, one
  row per observation);
* EIrate of every live candidate that is neither observed nor running
  (eqs. 3-6): ``EI = sd * tau((mu - best) / sd)`` against the owning
  tenant's incumbent, over the candidate's cost, where a tenant with no
  observation yet is held against the no-observation floor (its live
  peers' lowest prior mean less five of their largest prior sds);
* the best EIrate, and the relative gap by which the run's own pick lies
  below it (0 for the argmax or an exact tie).

After the window it also compares the run's final posterior over every
launchable candidate with its own.  Launch bookkeeping is checked on the
way: a launch of a candidate that is not live and unselected, an
observation that is not the ground truth, a warm start outside the
tenant's cheapest candidates, each counts as one error.

The control is the same reference one precision down, bfloat16 in place
of the float32 the configuration states: its posterior means and
variances rounded to bfloat16 and EIrate computed in bfloat16 arithmetic.
The harness puts it in the program's place: its pick at each checked
decision (the first bfloat16 argmax, tenants in arrival order) is judged
by the float64 scores like the run's, and its final posterior
(:meth:`Replay.control_posterior`) like the run's.
"""

from __future__ import annotations

import math

import ml_dtypes
import numpy as np
from scipy.special import ndtr

BF16 = ml_dtypes.bfloat16
_INV_SQRT_2PI = 1.0 / math.sqrt(2.0 * math.pi)


def _tau(u):
    return u * ndtr(u) + np.exp(-0.5 * u * u) * _INV_SQRT_2PI


def eirate(mu, var, best, cost):
    """EIrate = EI / cost in float64; zero variance degenerates to the
    improvement of the mean."""
    sd = np.sqrt(var)
    pos = sd > 0
    safe = np.where(pos, sd, 1.0)
    ei = np.where(pos, safe * _tau((mu - best) / safe),
                  np.maximum(mu - best, 0.0))
    return ei / cost


def eirate_bf16(mu, var, best, cost):
    """EIrate with every operand and every intermediate in bfloat16."""
    def r(x):
        return np.asarray(x, np.float32).astype(BF16)
    mu_b, sd_b, best_b, cost_b = r(mu), r(np.sqrt(r(var))), r(best), r(cost)
    pos = sd_b > 0
    safe = np.where(pos, sd_b, r(1.0))
    u = r((mu_b - best_b) / safe)
    phi_cdf = r(ndtr(u.astype(np.float32)))
    pdf = r(np.exp(r(-0.5 * u.astype(np.float32) ** 2).astype(np.float32))
            * np.float32(_INV_SQRT_2PI))
    tau = r(r(u * phi_cdf) + pdf)
    ei = np.where(pos, r(safe * tau), r(np.maximum(mu_b - best_b, r(0.0))))
    return r(ei / cost_b).astype(np.float64)


def rel_gap(best: float, value: float) -> float:
    """How far ``value`` lies below ``best``, relative to ``best``."""
    if value >= best:
        return 0.0
    if not np.isfinite(value) or best <= 0:
        return math.inf
    return (best - value) / best


class TenantRef:
    """One tenant's float64 GP state and its cached scores."""

    def __init__(self, key: int, K, mu0, cost, z_true, jitter: float):
        self.key = key
        self.K = np.asarray(K, np.float64)
        self.mu0 = np.asarray(mu0, np.float64)
        self.cost = np.asarray(cost, np.float64)
        self.z_true = np.asarray(z_true, np.float64)
        self.m = len(self.mu0)
        self.kdiag = np.diag(self.K).copy()
        self.prior_sd = float(np.sqrt(np.clip(self.kdiag, 0, None).max()))
        self.jitter = jitter
        self.live = False
        self.selected = np.zeros(self.m, bool)
        self.best: float | None = None
        self._W = np.zeros((min(self.m, 16), self.m))
        self._alpha = np.zeros(min(self.m, 16))
        self._mu_acc = np.zeros(self.m)      # sum_j alpha_j W_j
        self._var_acc = np.zeros(self.m)     # sum_j W_j ** 2
        self.k = 0
        self._scores: dict[str, tuple] = {}

    def observe(self, x: int, z: float) -> None:
        k = self.k
        if k == self._W.shape[0]:
            grow = min(self.m, 2 * k)
            self._W = np.vstack([self._W, np.zeros((grow - k, self.m))])
            self._alpha = np.concatenate([self._alpha, np.zeros(grow - k)])
        W, alpha = self._W[:k], self._alpha[:k]
        l = W[:, x]
        d = math.sqrt(max(self.K[x, x] + self.jitter - float(l @ l),
                          self.jitter))
        w = self._W[k] = (self.K[x] - l @ W) / d
        a = self._alpha[k] = (z - self.mu0[x] - float(l @ alpha)) / d
        self._mu_acc += a * w
        self._var_acc += w * w
        self.k = k + 1
        self.best = z if self.best is None else max(self.best, z)
        self._scores.clear()

    def posterior(self) -> tuple[np.ndarray, np.ndarray]:
        """(mean, variance) over the tenant's candidates."""
        return (self.mu0 + self._mu_acc,
                np.maximum(self.kdiag - self._var_acc, 0.0))

    def select(self, x: int) -> None:
        self.selected[x] = True
        self._scores.clear()

    def scores(self, floor: float, kind: str = "f64"):
        """(EIrate vector with selected masked to -inf, its max, argmax)."""
        hit = self._scores.get(kind)
        if hit is not None and hit[0] == floor:
            return hit[1:]
        mu, var = self.posterior()
        best = self.best if self.best is not None else floor
        fn = eirate if kind == "f64" else eirate_bf16
        s = np.where(self.selected, -np.inf, fn(mu, var, best, self.cost))
        i = int(np.argmax(s))
        self._scores[kind] = (floor, s, float(s[i]), i)
        return s, float(s[i]), i


class Replay:
    """Teacher-forced replay of one run (module docstring)."""

    def __init__(self, arrivals: dict, *, jitter: float, warm_start: int,
                 control: bool = False):
        self.arrivals = arrivals
        self.control = control
        self.jitter = jitter
        self.tenants: dict[int, TenantRef] = {}
        self.order: list[int] = []          # live tenants, arrival order
        self.warm_start = warm_start
        self.floor = 0.0
        self.errors: list[str] = []
        self.gaps: list[float] = []
        self.control_gaps: list[float] = []

    # ---- state changes ------------------------------------------------

    def _refloor(self) -> None:
        live = [self.tenants[k] for k in self.order]
        if not live:
            self.floor = 0.0
            return
        mu_min = min(float(t.mu0.min()) for t in live)
        sd_max = max(t.prior_sd for t in live)
        self.floor = mu_min - 5.0 * max(sd_max, 1e-3)

    def arrive(self, key: int) -> None:
        ev = self.arrivals[key]
        t = self.tenants[key] = TenantRef(key, ev.K_block, ev.mu0, ev.cost,
                                          ev.z_true, self.jitter)
        t.live = True
        self.order.append(key)
        self._refloor()

    def depart(self, key: int) -> None:
        t = self.tenants[key]
        if t.live:
            t.live = False
            self.order.remove(key)
            self._refloor()

    def finish(self, trial) -> None:
        t = self.tenants[trial.tenant_key]
        if not t.live:
            if trial.z is not None:
                self.errors.append(f"observation kept for departed tenant "
                                   f"{t.key}")
            return
        z = float(t.z_true[trial.local_model])
        if trial.z != z:
            self.errors.append(f"trial of tenant {t.key} model "
                               f"{trial.local_model} observed {trial.z}, "
                               f"ground truth {z}")
        t.observe(trial.local_model, z)

    def launch(self, trial, check: bool) -> None:
        t = self.tenants.get(trial.tenant_key)
        x = trial.local_model
        if t is None or not t.live or not 0 <= x < t.m or t.selected[x]:
            self.errors.append(f"launch of a candidate that is not live and "
                               f"unselected: {trial}")
            if check and trial.user_hint == -1:
                self.gaps.append(math.inf)
            return
        if trial.user_hint == -2:
            warm = np.argsort(t.cost, kind="stable")[:self.warm_start]
            if x not in warm:
                self.errors.append(f"warm start outside the cheapest "
                                   f"candidates: {trial}")
        elif check:
            self._check(t, x)
        t.select(x)

    # ---- the decision check -------------------------------------------

    def _argmax(self, kind: str) -> tuple[float, int, int]:
        best, who, idx = -math.inf, -1, -1
        for key in self.order:
            _, v, i = self.tenants[key].scores(self.floor, kind)
            if v > best:
                best, who, idx = v, key, i
        return best, who, idx

    def _check(self, t: TenantRef, x: int) -> None:
        best, _, _ = self._argmax("f64")
        s, _, _ = t.scores(self.floor)
        self.gaps.append(rel_gap(best, float(s[x])))
        if not self.control:
            return
        _, ckey, cidx = self._argmax("bf16")
        cs, _, _ = self.tenants[ckey].scores(self.floor)
        self.control_gaps.append(rel_gap(best, float(cs[cidx])))

    # ---- the final posterior --------------------------------------------

    def posterior_error(self, posterior) -> float:
        """Largest error, over launchable candidates of every live tenant,
        of ``posterior(key) -> (mu, var)`` against the reference, in prior
        sds (mean) and prior variances (variance)."""
        worst = 0.0
        for key in self.order:
            t = self.tenants[key]
            free = ~t.selected
            if not free.any():
                continue
            mu, var = t.posterior()
            mu_p, var_p = posterior(key)
            worst = max(worst,
                        float(np.max(np.abs(mu_p - mu)[free])) / t.prior_sd,
                        float(np.max(np.abs(var_p - var)[free]))
                        / t.prior_sd ** 2)
        return worst

    def control_posterior(self, key: int) -> tuple[np.ndarray, np.ndarray]:
        """The control's final posterior of a tenant: the reference's,
        rounded to bfloat16."""
        def low(x):
            return x.astype(np.float32).astype(BF16).astype(np.float64)
        mu, var = self.tenants[key].posterior()
        return low(mu), low(var)


def replay_run(arrivals: dict, processed, launches_before, trials, *,
               check, jitter: float, warm_start: int,
               control: bool = False) -> Replay:
    """Replay a run's processed events and launches in order.

    ``processed`` is the engine's log (``(index, t, kind, data)``),
    ``launches_before[i]`` the number of launches made before processed
    event ``i`` began, ``trials`` the run's trial list in launch order and
    ``check`` the set of trial indices whose decisions are checked.  With
    ``control`` the bfloat16 control's pick is read at each of them too."""
    rep = Replay(arrivals, jitter=jitter, warm_start=warm_start,
                 control=control)
    bounds = list(launches_before[:len(processed)]) + [len(trials)]
    for i, rec in enumerate(processed):
        kind, data = rec[2], rec[3]
        if kind == "arrive":
            rep.arrive(data[0])
        elif kind == "depart":
            rep.depart(data[0])
        elif kind == "finish":
            rep.finish(trials[data[2]])
        else:
            raise ValueError(f"the reference replays no {kind!r} events")
        for j in range(bounds[i], bounds[i + 1]):
            rep.launch(trials[j], j in check)
    return rep
