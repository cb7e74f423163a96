"""Gaussian-process posterior over a finite model set.

The paper (Supplemental A) conditions a GP prior ``GP(mu(x), k(x, x'))`` on
noise-free observations of a growing set of models.  Two engines are provided:

* :func:`posterior_masked` — one-shot, fixed-shape, fully jittable posterior
  over *all* models given an observation mask.  O(n^3); used for tests, small
  problems and as the oracle for the incremental engine.

* :class:`IncrementalGP` — event-driven engine used by the scheduler.  It
  maintains a Cholesky factor of the observed-set kernel and the matrix
  ``W = L^{-1} K[obs, :]`` so that appending one observation costs O(k * n)
  and the full posterior mean/variance over all n models is always available
  in O(1) extra work.  All buffers are preallocated at size n so every
  append is a fixed-shape jitted step (no recompilation as observations grow).

Observation noise is zero in the paper's setting (each model is run once);
``jitter`` keeps the Cholesky numerically PSD and is chosen far below any
kernel scale of interest (see DESIGN.md §3.3).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.obs.trace import NULL_TRACER, SCALAR_BYTES


DEFAULT_JITTER = 1e-6


def posterior_masked(
    K: jax.Array,
    mu0: jax.Array,
    z: jax.Array,
    mask: jax.Array,
    jitter: float = DEFAULT_JITTER,
) -> tuple[jax.Array, jax.Array]:
    """Posterior mean/variance over all n models given masked observations.

    Uses the identity-padding trick: rows/cols of unobserved models are
    replaced by identity rows, so the Cholesky of the padded matrix contains
    the Cholesky of ``K[obs, obs]`` embedded in the observed rows and the
    identity rows are inert (their RHS entries are zeroed).

    Args:
      K:    (n, n) prior covariance.
      mu0:  (n,) prior mean.
      z:    (n,) observed values; entries where ``mask`` is False are ignored.
      mask: (n,) bool, True where observed.
      jitter: diagonal jitter added to observed rows.

    Returns:
      (mu_post, var_post), each (n,).  For observed models the posterior mean
      equals z and the variance is ~0.
    """
    n = K.shape[0]
    m = mask.astype(K.dtype)
    eye = jnp.eye(n, dtype=K.dtype)
    A = K * (m[:, None] * m[None, :]) + eye * (1.0 - m) + eye * (jitter * m)
    L = jnp.linalg.cholesky(A)
    rhs = m * (z - mu0)
    alpha = jax.scipy.linalg.cho_solve((L, True), rhs)
    V = m[:, None] * K  # (n, n): column x holds K[obs, x] with unobserved rows zeroed
    W = jax.scipy.linalg.solve_triangular(L, V, lower=True)
    mu_post = mu0 + V.T @ alpha
    var_post = jnp.diag(K) - jnp.sum(W * W, axis=0)
    return mu_post, jnp.maximum(var_post, 0.0)


def rows_dot(v: jax.Array, M: jax.Array, rows) -> jax.Array:
    """``sum_{j < rows} v[j] * M[j]``, accumulated in row order.

    A dot product's summation order is the compiler's choice, and it
    differs between a jitted step and the same step batched under
    ``vmap``/``scan`` (``sim_batched``) and between backends.  The
    incremental Cholesky amplifies those last-bit differences into EIrate
    gaps of ~1e-4, enough to flip decisions, so the fold and the readout
    fix the order: the event engine and the batched engine then compute
    bit-identical posteriors.  Rows from ``rows`` on are zero in every
    caller, so bounding the loop there only skips exact ``+ 0`` terms."""
    def body(j, acc):
        return acc + v[j] * M[j]
    return jax.lax.fori_loop(0, rows, body, jnp.zeros(M.shape[1:], M.dtype))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _append_step(
    W: jax.Array,
    alpha: jax.Array,
    diag_acc: jax.Array,
    K_row: jax.Array,
    idx: jax.Array,
    z_val: jax.Array,
    mu0_val: jax.Array,
    k: jax.Array,
    jitter: jax.Array,
):
    """One fixed-shape incremental Cholesky/posterior update.

    W:        (n, n) buffer; rows [0, k) hold L^{-1} K[obs, :].
    alpha:    (n,) buffer; entries [0, k) hold L^{-1} (z_obs - mu0_obs).
    diag_acc: (n,) running sum of W^2 over observed rows (= prior_var - post_var).
    K_row:    (n,) row of the prior kernel for the new model.
    idx:      scalar int, index of the new model.

    Also returns the pivot ``d2`` (the Schur complement of the new row):
    when it sits at the jitter floor the factorization is numerically
    degenerate — the health plane's conditioning watchdog consumes it
    (DESIGN.md §14).  The extra output changes no numerics: W/alpha/
    diag_acc are computed exactly as before.
    """
    # l = L^{-1} K[obs, new] is exactly column `idx` of W (rows >= k are zero).
    l = W[:, idx]
    d2 = K_row[idx] + jitter - rows_dot(l, l, k)
    d = jnp.sqrt(jnp.maximum(d2, jitter))
    w_new = (K_row - rows_dot(l, W, k)) / d
    a_new = (z_val - mu0_val - rows_dot(l, alpha, k)) / d
    W = jax.lax.dynamic_update_index_in_dim(W, w_new, k, axis=0)
    alpha = alpha.at[k].set(a_new)
    diag_acc = diag_acc + w_new * w_new
    return W, alpha, diag_acc, d2


@jax.jit
def _readout(W, alpha, mu0, kdiag, diag_acc, k):
    # alpha @ W over the k observed rows (not W.T @ alpha): keeps the
    # (n, n) buffer row-major and avoids an eager 25MB transpose copy per
    # scheduler decision.
    mu = mu0 + rows_dot(alpha, W, k)
    var = jnp.maximum(kdiag - diag_acc, 0.0)
    return mu, var


@jax.jit
def _readout_into(mu_pool, var_pool, sd_pool, idx, W, alpha, mu0, kdiag,
                  diag_acc, k):
    """One block's readout (``_readout``'s own program, so the fold's row
    order) scattered at its global indices ``idx`` into the pool's device
    mean, variance and sd.  The pools are not donated: a caller may still
    hold the previous decision's arrays."""
    mu, var = _readout(W, alpha, mu0, kdiag, diag_acc, k)
    return (mu_pool.at[idx].set(mu), var_pool.at[idx].set(var),
            sd_pool.at[idx].set(jnp.sqrt(var)))


class IncrementalGP:
    """Incremental zero-noise GP posterior over a fixed finite model set."""

    def __init__(self, K, mu0, jitter: float = DEFAULT_JITTER):
        self.K = jnp.asarray(K)
        self.mu0 = jnp.asarray(mu0, dtype=self.K.dtype)
        n = self.K.shape[0]
        if self.K.shape != (n, n):
            raise ValueError(f"K must be square, got {self.K.shape}")
        if self.mu0.shape != (n,):
            raise ValueError(f"mu0 must be ({n},), got {self.mu0.shape}")
        self.n = n
        self.jitter = jnp.asarray(jitter, dtype=self.K.dtype)
        dtype = self.K.dtype
        self._W = jnp.zeros((n, n), dtype=dtype)
        self._alpha = jnp.zeros((n,), dtype=dtype)
        self._diag_acc = jnp.zeros((n,), dtype=dtype)
        self._k = 0
        self._kdiag = None
        self.observed: list[int] = []
        self._z = {}
        # pivot d² of the most recent fold, device-resident (never synced
        # unless a health monitor asks — the disabled path stays async)
        self.last_d2 = None

    def observe(self, idx: int, z_val: float) -> None:
        """Condition on z(model idx) = z_val.  O(n^2) fixed-shape jitted step."""
        if idx in self._z:
            raise ValueError(f"model {idx} already observed")
        import math
        if not math.isfinite(z_val):
            # poisoned-observation guard (DESIGN.md §16): a NaN/±inf fold
            # would silently corrupt every later posterior readout
            raise ValueError(f"non-finite observation {z_val!r} for "
                             f"model {idx}")
        self._W, self._alpha, self._diag_acc, self.last_d2 = _append_step(
            self._W,
            self._alpha,
            self._diag_acc,
            self.K[idx],
            jnp.asarray(idx),
            jnp.asarray(z_val, dtype=self.K.dtype),
            self.mu0[idx],
            jnp.asarray(self._k),
            self.jitter,
        )
        self._k += 1
        self.observed.append(idx)
        self._z[idx] = float(z_val)

    @property
    def num_observed(self) -> int:
        return self._k

    def fold_outputs(self, idx: int) -> tuple:
        """The buffers the most recent fold wrote (a timed fold waits on
        them); this engine holds every model, so ``idx`` picks nothing."""
        return self._W, self._alpha, self._diag_acc

    def resource_stats(self) -> dict:
        """Analytic byte/observation accounting of this engine's buffers
        (obs/accounting.py introspects through here, never through the
        private attributes).  ``alloc_bytes`` is the preallocated footprint
        — W (n,n) + K (n,n) + alpha/diag_acc/mu0 (n,) each; ``active_bytes``
        is the Cholesky-occupied share, the O(k·n) rows [0, k) of W plus k
        entries of alpha — the part that grows O(obs²) when n tracks the
        observed set."""
        item = self.K.dtype.itemsize
        n, k = self.n, self._k
        return {
            "models": n,
            "obs": k,
            "alloc_bytes": (2 * n * n + 3 * n) * item,
            "active_bytes": (k * n + k) * item,
            "dtype_bytes": item,
        }

    def readout_args(self) -> tuple:
        """The arguments of ``_readout`` (and the tail of
        ``_readout_into``'s): the buffers and the observation count."""
        if self._kdiag is None:
            self._kdiag = jnp.diag(self.K)
        return (self._W, self._alpha, self.mu0, self._kdiag, self._diag_acc,
                jnp.asarray(self._k))

    def posterior(self) -> tuple[jax.Array, jax.Array]:
        """(mu, var) over all n models, O(n^2) readout (jitted, row-major)."""
        return _readout(*self.readout_args())

    def posterior_sd(self) -> tuple[jax.Array, jax.Array]:
        mu, var = self.posterior()
        return mu, jnp.sqrt(var)


class BlockIncrementalGP:
    """Incremental GP specialized to block-diagonal priors.

    In the paper's experimental setting each "model" is an (algorithm,
    dataset) pair, so tenants' candidate sets are disjoint and K is block
    diagonal — observations for one tenant never move another tenant's
    posterior.  Exploiting that turns the per-event cost from O(n^2) to
    O(m^2) (m = block size, n = N*m total), a ~N x control-plane speedup
    measured in benchmarks/control_plane.py.  Same interface as
    :class:`IncrementalGP`; equivalence is tested in tests/test_gp.py.

    Blocks are also the unit of tenant churn (DESIGN.md §9): because each
    block owns an independent Cholesky factor, a tenant's covariance block
    can be appended (:meth:`add_block`) or retired (:meth:`retire_block`)
    at runtime without refactorizing any other tenant's state.  Retired
    entries keep their last posterior values in the cached readout; callers
    mask them (the streaming control plane marks them selected).

    The readout is kept twice over the whole capacity, each brought up to
    date by its own flush: a host cache (``_mu``/``_var``, :meth:`flush`;
    the sharded scorer and checkpoints read it) and a device pool of mean,
    variance and sd (:meth:`flush_device`; the device scorers read it),
    which a fold updates in place with one dispatch and no readback.
    ``_dirty`` and ``_dirty_dev`` name the blocks folded since each last
    saw them.  A change of the host cache's layout (admission, relocation,
    growth, a restore) drops the pool; the next device flush uploads the
    host cache once and recomputes the blocks it lacks.
    """

    def __init__(self, K=None, mu0=None, blocks: list | None = None,
                 jitter: float = DEFAULT_JITTER):
        import numpy as np
        self._jitter = jitter
        self.n = 0
        self._blocks: dict[int, np.ndarray] = {}
        self._engines: dict[int, IncrementalGP] = {}
        self._next_block_id = 0
        self._local: dict[int, tuple[int, int]] = {}
        self._mu = np.zeros(0, np.float32)
        self._var = np.zeros(0, np.float32)
        self._dirty: set[int] = set()
        self._pool = None       # device (mu, var, sd); None: upload anew
        self._dirty_dev: set[int] = set()
        self._idx_dev: dict[int, jax.Array] = {}    # block's global ids
        self.observed: list[int] = []
        self._z = {}
        self.last_d2 = None     # pivot d² of the most recent fold
        if K is not None:
            K = np.asarray(K)
            mu0 = np.asarray(mu0, dtype=K.dtype)
            n = K.shape[0]
            assert blocks is not None, "static construction requires blocks"
            idx = [np.asarray(b, dtype=np.int64) for b in blocks]
            seen = np.concatenate(idx)
            assert len(seen) == n and len(set(seen.tolist())) == n, \
                "blocks must partition the model set"
            for b in idx:
                self.add_block(b, K[np.ix_(b, b)], mu0[b])
            assert self.n == n

    @classmethod
    def empty(cls, jitter: float = DEFAULT_JITTER) -> "BlockIncrementalGP":
        """A dynamic instance with no tenants yet (streaming control plane)."""
        return cls(jitter=jitter)

    # ---- tenant churn: block lifecycle ------------------------------------

    def ensure_capacity(self, n_cap: int) -> None:
        """Grow the cached posterior readout to ``n_cap`` entries (padding:
        mu 0, var 0 — callers mask indices that belong to no block)."""
        import numpy as np
        if n_cap <= self.n:
            return
        grow = n_cap - self.n
        self._mu = np.concatenate([self._mu, np.zeros(grow, np.float32)])
        self._var = np.concatenate([self._var, np.zeros(grow, np.float32)])
        self.n = n_cap
        self._pool = None

    def add_block(self, indices, K_block, mu0_block) -> int:
        """Register one tenant's covariance block at the given global model
        indices.  O(m) setup; no other block is touched.  Returns a block id
        for :meth:`retire_block`."""
        import numpy as np
        b = np.asarray(indices, dtype=np.int64)
        K_block = np.asarray(K_block)
        mu0_block = np.asarray(mu0_block, dtype=K_block.dtype)
        m = len(b)
        assert K_block.shape == (m, m) and mu0_block.shape == (m,)
        clash = [int(g) for g in b if int(g) in self._local]
        assert not clash, f"indices already owned by a live block: {clash}"
        bid = self._next_block_id
        self._next_block_id += 1
        self.ensure_capacity(int(b.max()) + 1)
        self._blocks[bid] = b
        self._engines[bid] = IncrementalGP(K_block, mu0_block, self._jitter)
        for li, g in enumerate(b.tolist()):
            self._local[int(g)] = (bid, li)
        self._mu[b] = mu0_block.astype(np.float32)
        self._var[b] = np.clip(np.diag(K_block), 0, None).astype(np.float32)
        self._pool = None
        return bid

    def retire_block(self, block_id: int) -> None:
        """Drop one tenant's block: its Cholesky factor is freed and its
        models stop accepting observations.  Other blocks are untouched
        (no refactorization).  Cached readout entries go stale — mask them."""
        b = self._blocks.pop(block_id)
        self._engines.pop(block_id)
        self._dirty.discard(block_id)
        self._dirty_dev.discard(block_id)
        self._idx_dev.pop(block_id, None)
        for g in b.tolist():
            del self._local[int(g)]

    def relocate_block(self, block_id: int, new_indices) -> None:
        """Move a live block to new global indices (index-space compaction,
        DESIGN.md §10).  The Cholesky factor and every observation are
        position-independent (they live in block-local coordinates), so this
        is O(m) bookkeeping: remap the global->local index, move the cached
        readout values, and leave the vacated entries inert (mu 0, var 0 —
        the padding convention)."""
        import numpy as np
        old = self._blocks[block_id]
        new = np.asarray(new_indices, dtype=np.int64)
        assert new.shape == old.shape, "relocation must preserve block size"
        own = set(old.tolist())
        clash = [int(g) for g in new
                 if int(g) in self._local and int(g) not in own]
        assert not clash, f"target indices owned by a live block: {clash}"
        self.ensure_capacity(int(new.max()) + 1)
        for g in old.tolist():
            del self._local[int(g)]
        for li, g in enumerate(new.tolist()):
            self._local[int(g)] = (block_id, li)
        mu_b, var_b = self._mu[old].copy(), self._var[old].copy()
        self._mu[old] = 0.0
        self._var[old] = 0.0
        self._mu[new] = mu_b
        self._var[new] = var_b
        self._blocks[block_id] = new
        self._idx_dev.pop(block_id, None)
        self._pool = None

    @staticmethod
    def blocks_from_membership(K, membership, atol: float = 0.0) -> list | None:
        """Tenant partition if candidate sets are disjoint and K has no
        cross-block mass; None if the structure doesn't hold."""
        import numpy as np
        membership = np.asarray(membership, bool)
        if (membership.sum(axis=0) != 1).any():
            return None
        blocks = [np.nonzero(membership[i])[0] for i in range(membership.shape[0])]
        K = np.asarray(K)
        mask = np.zeros_like(K, dtype=bool)
        for b in blocks:
            mask[np.ix_(b, b)] = True
        if np.abs(K[~mask]).max(initial=0.0) > atol:
            return None
        return blocks

    def observe(self, idx: int, z_val: float) -> None:
        import math
        if not math.isfinite(z_val):
            # poisoned-observation guard at the block boundary too: callers
            # that bypass ControlPlane.record_observation get the same wall
            raise ValueError(f"non-finite observation {z_val!r} for "
                             f"model {idx}")
        if idx not in self._local:
            raise KeyError(f"model {idx} belongs to no live block")
        bi, li = self._local[idx]
        self._engines[bi].observe(li, z_val)
        self.last_d2 = self._engines[bi].last_d2
        self._dirty.add(bi)
        self._dirty_dev.add(bi)
        self.observed.append(idx)
        self._z[idx] = float(z_val)

    @property
    def num_observed(self) -> int:
        return len(self.observed)

    def fold_outputs(self, idx: int) -> tuple:
        """The buffers the most recent fold of model ``idx``'s block wrote."""
        return self._engines[self._local[idx][0]].fold_outputs(idx)

    @property
    def readout_nbytes(self) -> int:
        """Bytes of the host readout cache (``_mu`` + ``_var``, float32 over
        the full capacity): what one posterior upload moves."""
        return self._mu.nbytes + self._var.nbytes

    def resource_stats(self) -> dict:
        """Per-block + aggregate resource accounting (obs/accounting.py).

        ``blocks`` maps block id -> the owning :class:`IncrementalGP`'s
        :meth:`~IncrementalGP.resource_stats`; the aggregate adds the host
        readout caches (``_mu``/``_var``, float32 over the full capacity).
        Pure host-side introspection: no device syncs, so the accounting
        plane's disabled-path cost discipline holds."""
        blocks = {bid: eng.resource_stats()
                  for bid, eng in sorted(self._engines.items())}
        return {
            "blocks": blocks,
            "num_blocks": len(blocks),
            "capacity": self.n,
            "obs_total": sum(b["obs"] for b in blocks.values()),
            "alloc_bytes": sum(b["alloc_bytes"] for b in blocks.values()),
            "active_bytes": sum(b["active_bytes"] for b in blocks.values()),
            "readout_bytes": self.readout_nbytes,
        }

    def flush(self, tracer=NULL_TRACER) -> None:
        """Read the dirty blocks' posteriors back into the host readout
        cache, under a ``gp_flush`` span (attr ``blocks``).  Each block's
        readback is where the host first waits for its folds."""
        import numpy as np
        with tracer.span("gp_flush", blocks=len(self._dirty)):
            for bi in self._dirty:
                mu_b, var_b = self._engines[bi].posterior()
                b = self._blocks[bi]
                mu_h, var_h = np.asarray(mu_b), np.asarray(var_b)
                self._mu[b] = mu_h
                self._var[b] = var_h
                if tracer.enabled:
                    tracer.count("host_syncs", 2)
                    tracer.count("h2d_bytes", SCALAR_BYTES)  # readout's k
                    tracer.count("d2h_bytes", mu_h.nbytes + var_h.nbytes)
            self._dirty.clear()

    def restore_cache(self, mu, var, dirty) -> None:
        """Overwrite the host cache and its dirty set (a checkpoint's);
        the device pool is uploaded from them at the next device flush."""
        import numpy as np
        self._mu = np.array(mu, dtype=np.float32)
        self._var = np.array(var, dtype=np.float32)
        self._dirty = set(dirty)
        self._dirty_dev.clear()
        self._pool = None

    def flush_device(self, tracer=NULL_TRACER) -> tuple:
        """Bring the device pool up to date and return it as ``(mu, var,
        sd)``: one ``_readout_into`` dispatch for each block folded since
        the pool last saw it, under a ``gp_flush`` span (attr ``blocks``),
        with nothing read back.  A dropped pool is first uploaded from the
        host cache under ``posterior_upload`` (the sqrt on the device), and
        counted as ``pool_uploads`` on the enclosing span."""
        if self._pool is None:
            with tracer.span("posterior_upload"):
                mu, var = jnp.asarray(self._mu), jnp.asarray(self._var)
                self._pool = (mu, var, jnp.sqrt(var))
                # the blocks the host cache lacks
                self._dirty_dev |= self._dirty
                if tracer.enabled:
                    tracer.count("h2d_bytes", self.readout_nbytes)
                    tracer.sync(self._pool)
            if tracer.enabled:
                tracer.count("pool_uploads", 1)
        with tracer.span("gp_flush", blocks=len(self._dirty_dev)):
            for bi in self._dirty_dev:
                idx = self._idx_dev.get(bi)
                if idx is None:
                    idx = jnp.asarray(self._blocks[bi], dtype=jnp.int32)
                    self._idx_dev[bi] = idx
                    if tracer.enabled:
                        tracer.count("h2d_bytes", idx.nbytes)
                self._pool = _readout_into(
                    *self._pool, idx, *self._engines[bi].readout_args())
                if tracer.enabled:
                    tracer.count("h2d_bytes", SCALAR_BYTES)  # readout's k
            self._dirty_dev.clear()
        return self._pool

    def posterior(self):
        """(mu, var) of the device pool, brought up to date."""
        mu, var, _ = self.flush_device()
        return mu, var

    def posterior_host(self):
        """(mu, var) as the engine's own host numpy buffers (read-only by
        convention — callers must not mutate).  The sharded scorer consumes
        these directly: wrapping them in device arrays here only to convert
        back before the sharded upload would round-trip every decision."""
        self.flush()
        return self._mu, self._var

    def posterior_sd(self):
        """(mu, sd) of the device pool, brought up to date."""
        mu, _, sd = self.flush_device()
        return mu, sd


def make_gp(K, mu0, membership=None, jitter: float = DEFAULT_JITTER):
    """Pick the block engine when the tenant structure allows it."""
    if membership is not None:
        blocks = BlockIncrementalGP.blocks_from_membership(K, membership)
        if blocks is not None and len(blocks) > 1:
            return BlockIncrementalGP(K, mu0, blocks, jitter)
    return IncrementalGP(K, mu0, jitter)
