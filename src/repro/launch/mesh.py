"""Production mesh builders (dry-run target: TPU v5e, 256 chips/pod).

FUNCTIONS, not module constants: importing this module never touches jax
device state (jax locks the device count on first backend init).

``make_mesh_for_devices`` returns a :class:`MeshLayout` — the mesh plus the
RESOLVED (data, model) split that produced it. Callers used to get a bare
mesh with the model-parallel degree silently halved whenever it didn't
divide the device count; the resolved shape is now part of the return value,
and an explicitly requested degree that doesn't fit raises instead of
degrading (degrading stays opt-in for the elastic-restart path, which
documents "preserved when possible, else halved").
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from ..distributed.sharding import make_mesh


@dataclasses.dataclass(frozen=True)
class MeshLayout:
    """A (data, model) device mesh plus the shape that was actually built.

    ``requested_model`` is the caller's ask (0 = auto); ``degraded`` is True
    when an explicit request was halved down to a divisor (only possible
    with ``allow_degrade=True``).
    """

    mesh: jax.sharding.Mesh
    data: int
    model: int
    requested_model: int
    degraded: bool

    @property
    def shape(self) -> tuple[int, int]:
        return (self.data, self.model)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_for_devices(n_devices: int, model_parallel: int = 0, *,
                          allow_degrade: bool = False) -> MeshLayout:
    """Elastic variant: whatever devices are alive -> (data, model) layout.

    model_parallel <= 0 auto-picks (min(16, n) halved to the nearest
    divisor). An EXPLICIT degree that doesn't divide ``n_devices`` raises a
    ValueError naming both numbers — unless ``allow_degrade=True``
    (launch/elastic.py's restart path, where "preserved if possible, else
    halved" is the documented contract); the halving is then recorded in
    ``MeshLayout.degraded`` instead of happening silently.
    """
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    requested = model_parallel
    if model_parallel <= 0:
        model_parallel = min(16, n_devices)
        while n_devices % model_parallel:
            model_parallel //= 2
    elif n_devices % model_parallel:
        if not allow_degrade:
            raise ValueError(
                f"model_parallel={model_parallel} does not divide "
                f"n_devices={n_devices}; pick a divisor, or pass "
                f"allow_degrade=True to halve to the nearest one")
        while n_devices % model_parallel:
            model_parallel //= 2
    mesh = make_mesh((n_devices // model_parallel, model_parallel),
                     ("data", "model"))
    return MeshLayout(mesh=mesh, data=n_devices // model_parallel,
                      model=model_parallel, requested_model=requested,
                      degraded=requested > 0 and model_parallel != requested)


def make_tp_mesh(tp: int) -> jax.sharding.Mesh:
    """A 1-axis ("model",) mesh over the first ``tp`` devices — the serving
    tensor-parallel layout (DESIGN.md §16). Data parallelism in serving is
    process-level (ReplicaSet), so the serving mesh carries no data axis."""
    devs = jax.devices()
    if len(devs) < tp:
        raise RuntimeError(
            f"tp={tp} needs {tp} devices, host has {len(devs)} "
            f"(XLA_FLAGS=--xla_force_host_platform_device_count=N simulates "
            f"more on CPU)")
    return jax.sharding.Mesh(np.asarray(devs[:tp]), ("model",))
