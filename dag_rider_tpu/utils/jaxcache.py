"""JAX process set-up shared by every entry point: where the persistent
compile cache lives, and which platform a device path may run on.

The limb-field/curve programs cost tens of seconds each to compile; the
test suite, the bench, the smoke, a node and a sidecar all want the same
cache so repeated runs skip XLA entirely. One rule, applied in one
place: ``dag_rider_tpu/ops/__init__.py`` calls
:func:`enable_persistent_cache` when the first device module loads;
nothing else does.
"""

from __future__ import annotations

import os

_DEFAULT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_persistent_cache() -> None:
    """Turn on JAX's persistent compilation cache and cache every entry
    regardless of size or compile time. Where ``JAX_COMPILATION_CACHE_DIR``
    is set the directory is the caller's and is left alone; otherwise it
    is ``<checkout>/.jax_cache`` — fixed, because the path is part of the
    cache key."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _DEFAULT)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def cpu_requested() -> bool:
    """True when ``JAX_PLATFORMS`` names ``cpu`` first — tests and CI,
    the one case in which a device path may run on the CPU backend."""
    first = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
    return first.strip().lower() == "cpu"


def device_platform() -> str:
    """Platform of jax's default backend, refusing a CPU nobody asked
    for: jax falls back to the CPU when the accelerator fails to
    initialise, and a validator must not then start and serve at CPU
    speed."""
    import jax

    platform = jax.devices()[0].platform
    if platform == "cpu" and not cpu_requested():
        raise RuntimeError(
            "jax is on the CPU backend but JAX_PLATFORMS does not name "
            "cpu: the accelerator failed to initialise (set "
            "JAX_PLATFORMS=cpu to run the device path on the CPU on "
            "purpose)"
        )
    return platform
