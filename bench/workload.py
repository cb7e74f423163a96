"""The traffic generator: one seeded tenant population from a config's spec.

A configuration file's ``tenants`` object says how tenants arrive, how long
they stay, how many candidates each brings, where those candidates sit in
the hyperparameter space, the prior over them and what a trial costs.
:func:`make_trace` turns that spec and ``--seed`` into the program's own
input, a ``ChurnTrace`` of ``TenantArrive`` / ``TenantDepart``
events.  The same spec and seed always give the same trace.

The Poisson/Pareto/Zipf path draws from its generator in the same order
as ``repro.stream.workload.poisson_churn_trace`` (copied here so that a
change to the program cannot move the benchmark's traffic), so for the
same seed it yields the same events; ``tests/bench`` holds that equality.

Spaces:

* ``{"dims": 1, "points": "linspace"}``: the m candidates of a tenant sit
  on an even grid of [0, 1]; every tenant of size m shares one prior block
  (the Fig-5 / Ease.ml synthetic setting).  Built on the host in float64.
* ``{"dims": d, "points": "uniform"}``: each tenant's candidates are m
  seeded points of the unit d-cube (an LCBench-style configuration space),
  so every tenant has its own prior block.  Needs a fixed candidate count;
  the blocks and the ground-truth draws are made on the device in one
  jitted call and brought to the host once.
* ``{"points": "grid", "levels": [l_1, ..., l_d]}``: the candidates are
  every combination of d categorical variables, variable i taking one of
  l_i levels, in C order (candidate ``sum_i a_i * prod_{j>i} l_j``: the
  last variable changes fastest), as in a cell search space such as
  NAS-Bench-201's.  Every tenant holds the same candidates and shares one
  float64 prior block.  Needs a fixed candidate count of ``prod l_i``.

Kernels: ``{"kind": "matern52", "length_scale", "variance"}`` on the
``linspace`` and ``uniform`` spaces, ``{"kind": "hamming", "length_scale",
"variance"}`` on ``grid`` (:func:`hamming_factors`); any other pairing is
an error.

Ground truth is one draw from the tenant's prior, shifted to be
non-negative, as in the paper's synthetic workload.
"""

from __future__ import annotations

import functools

import numpy as np

#: diagonal jitter of the prior block itself (as the program's own
#: generator adds), and the extra jitter that keeps the float32 Cholesky of
#: a d-cube block finite when the ground truth is drawn on the device
BLOCK_JITTER = 1e-10
DRAW_JITTER = 1e-4


def matern52(X: np.ndarray, Y: np.ndarray, length_scale: float,
             variance: float) -> np.ndarray:
    """Matérn nu=5/2 kernel between point sets X (a, d) and Y (b, d)."""
    d2 = ((X[:, None, :] - Y[None, :, :]) ** 2).sum(-1)
    r = np.sqrt(np.maximum(d2, 0.0)) / length_scale
    s5 = np.sqrt(5.0) * r
    return variance * (1.0 + s5 + 5.0 * r * r / 3.0) * np.exp(-s5)


def hamming_factors(levels, length_scale: float, variance: float):
    """The exponential Hamming kernel on a grid, by its factors.

    ``k(a, b) = variance * exp(-H(a, b) / length_scale)``, where ``H``
    counts the variables in which ``a`` and ``b`` differ, equals
    ``variance * prod_i rho ** [a_i != b_i]`` with ``rho = exp(-1 /
    length_scale)``: the block is exactly ``C_1 (x) ... (x) C_d`` with
    ``C_i = variance ** (1/d) * ((1 - rho) I + rho 1 1^T)`` of size
    ``l_i x l_i`` (the per-variable product form of categorical kernels
    such as COMBO's diffusion kernel on complete graphs, Oh et al.,
    arXiv 1902.00448).  Each ``C_i`` has eigenvalues ``1 - rho`` and
    ``1 - rho + l_i rho`` (times ``variance ** (1/d)``), so the block is
    positive definite, its smallest eigenvalue ``variance * (1 -
    rho) ** d`` (condition number about 3,600 at length 1 over 6
    variables of 5 levels): no jitter is added to it.

    Returns the factors ``C_i`` and their Cholesky factors ``L_i``, in
    float64; ``(x) L_i`` is a Cholesky factor of the block.
    """
    rho = float(np.exp(-1.0 / length_scale))
    scale = variance ** (1.0 / len(levels))
    Cs = [scale * ((1.0 - rho) * np.eye(n) + rho * np.ones((n, n)))
          for n in levels]
    return Cs, [np.linalg.cholesky(C) for C in Cs]


def kron_apply(factors, x: np.ndarray) -> np.ndarray:
    """``(F_1 (x) ... (x) F_d) x`` without forming the product: each
    ``F_i`` applied along its own axis of ``x`` reshaped to the grid."""
    y = x.reshape([f.shape[1] for f in factors])
    for axis, f in enumerate(factors):
        y = np.moveaxis(np.tensordot(f, y, axes=(1, axis)), 0, axis)
    return y.reshape(-1)


def _linspace_block(m: int, kernel: dict) -> tuple[np.ndarray, np.ndarray]:
    xs = np.linspace(0.0, 1.0, m)[:, None]
    K = matern52(xs, xs, kernel["length_scale"], kernel["variance"])
    K += BLOCK_JITTER * np.eye(m)
    return K, np.linalg.cholesky(K)


@functools.lru_cache(maxsize=None)
def _cube_program(tenants: int, m: int, dims: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def blocks(key, length_scale, variance):
        kx, kz = jax.random.split(key)
        X = jax.random.uniform(kx, (tenants, m, dims), jnp.float32)
        d2 = jnp.zeros((tenants, m, m), jnp.float32)
        for k in range(dims):     # exact squared distances, no dot
            diff = X[:, :, None, k] - X[:, None, :, k]
            d2 = d2 + diff * diff
        r = jnp.sqrt(d2) / length_scale
        s5 = jnp.sqrt(5.0) * r
        K = variance * (1.0 + s5 + 5.0 * r * r / 3.0) * jnp.exp(-s5)
        K = K + BLOCK_JITTER * jnp.eye(m, dtype=K.dtype)
        L = jnp.linalg.cholesky(K + DRAW_JITTER * variance
                                * jnp.eye(m, dtype=K.dtype))
        eps = jax.random.normal(kz, (tenants, m), jnp.float32)
        z = jnp.einsum("tij,tj->ti", L, eps,
                       precision=jax.lax.Precision.HIGHEST)
        return K, z - z.min(axis=1, keepdims=True)

    return blocks


def _cube_blocks(rng: np.random.Generator, tenants: int, m: int,
                 dims: int, kernel: dict) -> tuple[np.ndarray, np.ndarray]:
    import jax
    key = jax.random.key(int(rng.integers(0, 2**31 - 1)))
    K, z = _cube_program(tenants, m, dims)(
        key, np.float32(kernel["length_scale"]),
        np.float32(kernel["variance"]))
    K, z = np.asarray(K), np.asarray(z, np.float64)
    if not (np.isfinite(K).all() and np.isfinite(z).all()):
        raise ValueError("prior blocks or ground truth are not finite")
    return K, z


def _sizes(rng: np.random.Generator, spec: dict, count: int) -> np.ndarray:
    if spec["kind"] == "fixed":
        return np.full(count, int(spec["count"]))
    if spec["kind"] == "zipf":
        raw = rng.zipf(spec["s"], size=count)
        return np.clip(spec["min"] * raw, spec["min"], spec["max"]).astype(int)
    raise ValueError(f"unknown candidate-set kind {spec['kind']!r}")


def _cost(rng: np.random.Generator, spec: dict, m: int) -> np.ndarray:
    if spec["kind"] == "uniform":
        return np.ones(m)
    if spec["kind"] == "lognormal":
        return rng.lognormal(mean=0.0, sigma=spec["sigma"], size=m)
    raise ValueError(f"unknown cost kind {spec['kind']!r}")


def mean_cost(spec: dict) -> float:
    """Expected trial cost under a cost spec."""
    if spec["kind"] == "uniform":
        return 1.0
    if spec["kind"] == "lognormal":
        return float(np.exp(spec["sigma"] ** 2 / 2.0))
    raise ValueError(f"unknown cost kind {spec['kind']!r}")


def make_trace(tenants: dict, seed: int, name: str = "trace"):
    """The seeded ``ChurnTrace`` for a config's ``tenants`` spec."""
    from repro.stream import ChurnTrace, TenantArrive, TenantDepart

    rng = np.random.default_rng(seed)
    # the schedule (arrival times, session lengths, candidate-set sizes)
    # comes from its own fixed stream when the spec names one, so that
    # every seed gets the same work and differs in the data
    srng = (np.random.default_rng(tenants["structure_seed"])
            if "structure_seed" in tenants else rng)
    arr, ses = tenants["arrivals"], tenants["sessions"]
    count = int(arr["count"])
    if arr["kind"] == "poisson":
        arrive_at = np.cumsum(srng.exponential(1.0 / arr["rate"],
                                               size=count))
    elif arr["kind"] == "at_start":
        arrive_at = np.zeros(count)
    else:
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    if ses["kind"] == "pareto":
        lengths = (1.0 + srng.pareto(ses["alpha"], size=count)) \
            * ses["scale"]
    elif ses["kind"] == "none":
        lengths = None
    else:
        raise ValueError(f"unknown session kind {ses['kind']!r}")
    sizes = _sizes(srng, tenants["candidates"], count)

    space, kernel = tenants["space"], tenants["kernel"]
    pair = (space["points"], kernel["kind"])
    if pair not in (("linspace", "matern52"), ("uniform", "matern52"),
                    ("grid", "hamming")):
        raise ValueError(f"no kernel {kernel['kind']!r} on a "
                         f"{space['points']!r} space")
    cube = grid = None
    if space["points"] == "uniform":
        if len(set(sizes.tolist())) != 1:
            raise ValueError("uniform d-cube points need a fixed "
                             "candidate count")
        cube = _cube_blocks(rng, count, int(sizes[0]), int(space["dims"]),
                            kernel)
    elif space["points"] == "grid":
        levels = [int(n) for n in space["levels"]]
        if set(sizes.tolist()) != {int(np.prod(levels))}:
            raise ValueError(f"a grid of levels {levels} needs a fixed "
                             f"candidate count of {int(np.prod(levels))}")
        Cs, Ls = hamming_factors(levels, kernel["length_scale"],
                                 kernel["variance"])
        grid = functools.reduce(np.kron, Cs), Ls
    elif space["dims"] != 1:
        raise ValueError(f"unknown space {space!r}")

    chol: dict[int, tuple[np.ndarray, np.ndarray]] = {}
    events = []
    for i in range(count):
        m = int(sizes[i])
        if grid is not None:
            K = grid[0]              # one block object for every tenant
            z = kron_apply(grid[1], rng.standard_normal(m))
            z = z - z.min()
        elif cube is None:
            if m not in chol:
                chol[m] = _linspace_block(m, kernel)
            K, L = chol[m]
            z = L @ rng.standard_normal(m)
            z = z - z.min()
        else:
            K, z = cube[0][i], cube[1][i]
        events.append(TenantArrive(
            at=float(arrive_at[i]), tenant_key=i, K_block=K,
            mu0=np.zeros(m), cost=_cost(rng, tenants["cost"], m), z_true=z))
        if lengths is not None:
            events.append(TenantDepart(at=float(arrive_at[i] + lengths[i]),
                                       tenant_key=i))
    events.sort(key=lambda e: e.at)
    return ChurnTrace(events=tuple(events), name=name)
