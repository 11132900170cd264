"""Device mesh construction for the aggregation tier.

One logical axis, ``shard``: span-hash data parallelism (SURVEY.md §2.8).
A second axis is deliberately absent — every cross-shard interaction is a
commutative sketch merge, so a flat ring over ICI is the whole topology.

This is also the one place a device is chosen, so it is where a machine
without a TPU is refused (unless ``JAX_PLATFORMS=cpu`` says the CPU is
meant), and where the persistent compile cache is placed.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh

SHARD_AXIS = "shard"

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def cpu_requested() -> bool:
    """True when the environment names the CPU backend explicitly — the
    tests (tests/conftest.py) and the CPU docker image set this."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def require_tpu(devices: Sequence[jax.Device]) -> None:
    """Refuse to run the device tier on anything but a TPU unless the
    CPU was asked for: a chip that failed to initialise must not turn
    into a silent CPU server."""
    platform = devices[0].platform
    if platform != "tpu" and not cpu_requested():
        raise RuntimeError(
            f"zipkin-tpu's device tier needs a TPU, but JAX found "
            f"platform {platform!r} ({len(devices)} device(s)). Set "
            f"JAX_PLATFORMS=cpu to run on the CPU on purpose."
        )


def make_mesh(
    n_devices: Optional[int] = None, devices: Optional[Sequence[jax.Device]] = None
) -> Mesh:
    """A 1-D mesh over ``n_devices`` (default: all local devices)."""
    import numpy as np

    if devices is None:
        devices = jax.devices()
    require_tpu(devices)
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"requested {n_devices} devices, have {len(devices)}"
            )
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (SHARD_AXIS,))


def compile_cache_dir() -> str:
    """Where compiled programs persist: ``JAX_COMPILATION_CACHE_DIR`` if
    the environment sets it, else ``<checkout>/.jax_cache``. The path is
    part of the cache key, so it is never a temp name, pid or time."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT, ".jax_cache"
    )


def enable_compile_cache() -> Optional[str]:
    """Turn JAX's persistent compile cache on before the first program
    is built; returns the directory in use (None where it stays off).

    With ``JAX_COMPILATION_CACHE_DIR`` set JAX configures itself from
    the environment and nothing is set here. Under ``JAX_PLATFORMS=cpu``
    (the tests) the cache stays off: described-topology compiles write
    entries no process without a chip can read back."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return compile_cache_dir()
    if cpu_requested():
        return None
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
