"""Production mesh definition.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — critical because the
dry-run pins ``xla_force_host_platform_device_count=512`` before first init
while tests/benches must see the single real CPU device.

Production target: TPU v5e pods of 16x16 = 256 chips; the multi-pod mesh
stacks 2 pods (512 chips) along a leading "pod" axis used for cross-pod data
parallelism (DCI domain).  The same code scales to more pods by changing the
leading extent — the scheduler fleet (repro.core.fleet) slices whichever mesh
it is handed.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pod: int | None = None):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count)."""
    if pod:
        return _make_mesh((pod, data, model), ("pod", "data", "model"))
    return _make_mesh((data, model), ("data", "model"))


def make_scoring_mesh(num_shards: int | None = None):
    """1-D ("shard",) mesh for the sharded GP-EI scoring plane
    (repro.shardgp): the model axis of the control-plane state is
    partitioned over these devices.  Defaults to every visible device; the
    control plane's decision path is exact for any extent (DESIGN.md §10),
    so shrinking the mesh is a capacity knob, not a correctness one."""
    devices = jax.devices()
    n = len(devices) if num_shards is None else num_shards
    if not 1 <= n <= len(devices):
        raise ValueError(
            f"num_shards must be in [1, {len(devices)}], got {n}")
    from jax.sharding import Mesh
    return Mesh(np.asarray(devices[:n]), ("shard",))
