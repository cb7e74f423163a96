"""Device-time attribution: capture windows, shard skew, dispatch cost.

The capacity plane's timing half (DESIGN.md §15).  ``obs/accounting.py``
answers *where the bytes are*; this module answers *where the device time
goes* — specifically, why BENCH_shard_scale.json's weak scaling collapses
(efficiency 0.16 at 8 shards).  Three probes, composed by
``benchmarks/capacity.py`` into BENCH_capacity.json rows that decompose
the weak-scaling gap into named causes:

* :func:`capture` — a ``jax.profiler`` capture-window context manager
  around any region; the resulting TensorBoard/Perfetto trace carries the
  ``jax.named_scope`` phase annotations the scoring programs already emit
  (``gp_readout`` / ``score_topk`` / ``all_gather``).  A capture that was
  asked for and cannot start raises: it never degrades to an untraced run.
* :func:`per_shard_skew` — runs one caller-built thunk pinned to each
  device of a scoring mesh (single-device sub-meshes) and reports the
  per-device timing spread.  On forced host-platform devices the "devices"
  share physical cores, so the spread measures exactly the contention +
  imbalance a real multi-chip mesh hides inside its slowest-shard barrier.
* :func:`dispatch_overhead_us` — times a trivially small ``shard_map``
  program on the real mesh: all compute rounds to zero, what remains is
  the per-call dispatch + partitioning overhead that one fused decision
  pays regardless of |L|.

Everything here is host-side benchmarking machinery: nothing is wired into
the engines, nothing feeds a decision, and jax is imported lazily so the
obs package keeps its zero-dependency envelope.
"""

from __future__ import annotations

import contextlib
import time as _time

PROFILE_SCHEMA_VERSION = 1


@contextlib.contextmanager
def capture(logdir: str | None = None):
    """``jax.profiler`` capture window: everything inside the ``with``
    lands in a TensorBoard/Perfetto trace under ``logdir``.  Yields True
    while a capture runs, False when ``logdir`` is None.  When a
    ``logdir`` was given and ``start_trace`` fails, the error propagates:
    a measurement that asked for a trace must not run without one."""
    if logdir is None:
        yield False
        return
    from jax import profiler
    profiler.start_trace(str(logdir))
    try:
        yield True
    finally:
        profiler.stop_trace()


def time_us_blocked(fn, *, iters: int = 10, warmup: int = 2) -> float:
    """Mean wall µs per call with a ``block_until_ready`` barrier after
    every call — async dispatch must not let timings overlap."""
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn())
    t0 = _time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn())
    return (_time.perf_counter() - t0) / iters * 1e6


def single_device_mesh(device):
    """A one-device ``("shard",)`` mesh pinned to ``device`` — the same
    axis name the scoring programs expect, so a thunk built against it runs
    the genuine single-shard program on exactly that device."""
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.asarray([device]), ("shard",))


def per_shard_skew(make_thunk, devices=None, *, iters: int = 10,
                   warmup: int = 2) -> dict:
    """Per-device timing spread of one shard's workload.

    ``make_thunk(shard_index, mesh)`` builds a zero-arg callable running
    that shard's slice of work on the given single-device mesh (state
    construction happens inside the builder, outside the timed region).
    Returns the per-device µs plus the same max/mean skew index the layout
    plane uses for slots (``ShardLayout.imbalance``), so byte imbalance
    and time imbalance read on one scale.
    """
    import jax
    if devices is None:
        devices = jax.devices()
    per: list[float] = []
    for s, dev in enumerate(devices):
        thunk = make_thunk(s, single_device_mesh(dev))
        per.append(time_us_blocked(thunk, iters=iters, warmup=warmup))
    mean = sum(per) / len(per)
    return {"schema_version": PROFILE_SCHEMA_VERSION,
            "per_shard_us": per,
            "mean_us": mean, "max_us": max(per), "min_us": min(per),
            "skew": max(per) / mean if mean > 0 else 1.0}


def dispatch_overhead_us(mesh, *, iters: int = 50, warmup: int = 5) -> float:
    """Per-call overhead of dispatching a ``shard_map`` program on ``mesh``:
    the program's compute (one add over S floats) rounds to zero, so the
    measured time is partitioning + launch + the cross-device sync — the
    fixed cost every fused decision pays before any real work."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    @jax.jit
    def trivial(x):
        def local(x):
            return x + 1.0
        return jax.shard_map(local, mesh=mesh, in_specs=(P("shard"),),
                             out_specs=P("shard"), check_vma=False)(x)

    x = jax.device_put(jnp.zeros(mesh.devices.size, jnp.float32),
                       NamedSharding(mesh, P("shard")))
    return time_us_blocked(lambda: trivial(x), iters=iters, warmup=warmup)


__all__ = ["capture", "time_us_blocked",
           "single_device_mesh", "per_shard_skew", "dispatch_overhead_us",
           "PROFILE_SCHEMA_VERSION"]
