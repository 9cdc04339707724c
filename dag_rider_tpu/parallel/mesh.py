"""Device mesh helpers.

The reference's only comm stack is an in-memory channel broker
(``process/transport.go``) — host-side consensus traffic stays host-side
here too (gRPC / in-memory Transport). What *does* scale across chips is
the crypto batch work (SURVEY.md §2b): verify batches shard over a 1-D
"batch" mesh (data-parallel over a round's <= n vertices), and large-n MSM
work shards the same way. Collectives ride ICI via XLA — there is no
hand-written NCCL/MPI analog to port.
"""

from __future__ import annotations

import os
import warnings
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from dag_rider_tpu import config
from dag_rider_tpu.utils.jaxcache import cpu_requested


def make_mesh(
    n_devices: Optional[int] = None,
    shape: Optional[Tuple[int, ...]] = None,
    axis_names: Tuple[str, ...] = ("batch",),
) -> Mesh:
    """A device mesh over the first ``n_devices`` (default: all).

    shape defaults to 1-D ``(n_devices,)`` — verify batches are purely
    data-parallel, so one axis is the common case; pass e.g. shape=(4, 2),
    axis_names=("batch", "shard") to split MSM work within a batch row.
    """
    devs = jax.devices()
    if n_devices is None:
        n_devices = len(devs)
    devs = devs[:n_devices]
    if shape is None:
        shape = (n_devices,)
    import numpy as np

    return Mesh(np.asarray(devs).reshape(shape), axis_names)


def mesh_from_env(default_devices: int = 8) -> Mesh:
    """The 1-D batch mesh for ``verifier: "sharded"`` deployments.

    ``DAGRIDER_MESH`` gives the batch-axis device count; unset means
    every visible device. Only when ``JAX_PLATFORMS`` names ``cpu``
    (tests, CI) and the backend has not been initialized yet, the XLA
    host-device-count flag is injected first so the request still
    yields a real multi-device mesh — the virtual 8-device mesh the
    tests run on. Asking for more devices than jax has is an error on
    an accelerator (a four-chip deployment that came up with one chip
    must not serve at a quarter of its capacity); on the CPU platform
    the mesh clamps with a warning, because the flag above is ignored
    once jax has initialized."""
    want = config.env_opt_int("DAGRIDER_MESH")
    flags = os.environ.get("XLA_FLAGS", "")
    if cpu_requested() and "xla_force_host_platform_device_count" not in flags:
        virtual = want if want is not None else default_devices
        if virtual > 1:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={virtual}"
            ).strip()
    have = jax.device_count()
    if want is None:
        want = have
    if want > have:
        platform = jax.devices()[0].platform
        if platform != "cpu":
            raise RuntimeError(
                f"DAGRIDER_MESH={want} but jax sees {have} {platform} "
                f"device(s); refusing to serve on a smaller mesh"
            )
        warnings.warn(
            f"DAGRIDER_MESH={want} but only {have} device(s) visible; "
            f"clamping the mesh to {have}",
            stacklevel=2,
        )
        want = have
    return make_mesh(want)


def batch_sharding(mesh: Mesh, axis: str = "batch") -> NamedSharding:
    """Shard a batch-leading array over the mesh's batch axis."""
    return NamedSharding(mesh, PartitionSpec(axis))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())
