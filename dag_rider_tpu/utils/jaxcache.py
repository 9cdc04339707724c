"""JAX process set-up shared by every entry point: where the persistent
compile cache lives, where programs stored in their lowered form live
beside it, and which platform a device path may run on.

The limb-field/curve programs cost tens of seconds each to compile; the
test suite, the bench, the smoke, a node and a sidecar all want the same
cache so repeated runs skip XLA entirely. One rule, applied in one
place: ``dag_rider_tpu/ops/__init__.py`` calls
:func:`enable_persistent_cache` when the first device module loads;
nothing else does.

XLA's cache is keyed by the lowered module, so it cannot spare the
Python trace that produces one. A caller that can name a program by
what its trace reads keeps the lowered program itself under that name
(:func:`write_program`, :func:`read_program`), in the ``programs``
subdirectory of the same directory.
"""

from __future__ import annotations

import hashlib
import os
import tempfile
from typing import Optional

_DEFAULT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)
#: bytes of the sha256 digest that leads every stored program
_DIGEST_BYTES = 32


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


def program_dir() -> Optional[str]:
    """The ``programs`` subdirectory of the persistent compile cache's
    directory; None where no directory is set."""
    import jax

    root = jax.config.jax_compilation_cache_dir
    return os.path.join(root, "programs") if root else None


def read_program(name: str) -> Optional[bytes]:
    """The bytes stored under ``name``; None where nothing is, or where
    what is there is not whole (its leading digest does not match)."""
    root = program_dir()
    if root is None:
        return None
    try:
        with open(os.path.join(root, name), "rb") as fh:
            data = fh.read()
    except OSError:
        return None
    digest, payload = data[:_DIGEST_BYTES], data[_DIGEST_BYTES:]
    if hashlib.sha256(payload).digest() != digest:
        return None
    return payload


def write_program(name: str, payload: bytes) -> None:
    """Store ``payload`` under ``name``, whole or not at all: it goes to a
    file of its own in the same directory, which then replaces the name in
    one rename, so neither a reader nor a second writer of the same name
    ever meets a part. A store that fails leaves nothing and raises
    nothing: the program is simply not kept."""
    root = program_dir()
    if root is None:
        return
    try:
        os.makedirs(root, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=root, prefix=name + ".", suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as fh:
                fh.write(hashlib.sha256(payload).digest())
                fh.write(payload)
            os.replace(tmp, os.path.join(root, name))
        except BaseException:
            os.unlink(tmp)
            raise
    except OSError:
        pass


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
