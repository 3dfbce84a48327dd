"""In-process JAX platform selection and the one compilation-cache rule.

There is one installation: the CPU-only sandbox (tests, rehearsals) and the
machine with the chip carry the same Python, JAX and packages, and the chip
is a plain local TPU that JAX finds by itself. JAX honours ``JAX_PLATFORMS``
from the environment, so an entry point that takes no ``--platform`` option
pins nothing. :func:`pin_platform` is for the two cases an entry point has
to decide in code, before the first backend touch: ``"cpu"`` (optionally
with a virtual device count, the multi-chip rehearsal) and ``"tpu"``
literally (fail at start-up if there is no chip, never carry on on the CPU).

A chip belongs to one process at a time: a process that has touched JAX
holds it, and a child that needs it then fails or hangs. Entry points
therefore run everything in one process.

:func:`enable_compile_cache` is the single rule for the persistent
compilation cache, called by ``chip_smoke.py``, ``benchmark_runner.py``
and ``tests/conftest.py`` alike.
"""

from __future__ import annotations

import os
import re
from typing import Optional

# <checkout>/spark_rapids_ml_tpu/utils/platform.py -> <checkout>
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def pin_platform(
    platform: Optional[str] = None, host_device_count: Optional[int] = None
) -> None:
    """Pin the JAX platform in-process, before any backend is initialized.

    Parameters
    ----------
    platform:
        ``"cpu"``, ``"tpu"`` or ``None``. ``None`` pins nothing: JAX reads
        ``JAX_PLATFORMS`` itself and otherwise picks its default backend
        (the TPU where there is one). Any other name is an error.
    host_device_count:
        When rehearsing a multi-chip mesh on the CPU, the number of virtual
        host devices (``--xla_force_host_platform_device_count``). Applied
        via ``XLA_FLAGS`` before backend init; replaces a count already
        present there.

    Must be called before the first ``jax.devices()`` / array op. Calling it
    after a different backend was initialized raises ``RuntimeError`` rather
    than silently pinning nothing.
    """
    if platform not in (None, "cpu", "tpu"):
        raise ValueError(
            f"pin_platform: platform must be 'cpu', 'tpu' or None, got {platform!r}"
        )
    if host_device_count is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        flag = f"--xla_force_host_platform_device_count={host_device_count}"
        if "xla_force_host_platform_device_count" in flags:
            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+", flag, flags
            )
        else:
            flags = f"{flags} {flag}".strip()
        os.environ["XLA_FLAGS"] = flags
    if platform is None:
        return

    import jax

    if backend_initialized():
        current = jax.local_devices()[0].platform
        if current != platform:
            raise RuntimeError(
                f"pin_platform({platform!r}) called after the {current!r} backend "
                "was initialized; pin before the first jax.devices()/array op"
            )
        return
    # the env write is for child processes (none of which may need the chip)
    os.environ["JAX_PLATFORMS"] = platform
    jax.config.update("jax_platforms", platform)


def backend_initialized() -> bool:
    """True if any jax backend has already been created in this process."""
    from jax._src import xla_bridge

    return bool(xla_bridge._backends)


def enable_compile_cache(min_compile_secs: float = 1.0) -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
    directory is set in code. Where it is not, the cache lives at the fixed
    ``<checkout>/.jax_cache`` (git-ignored) — never a temporary name, pid
    or time, because the path is part of the cache key and a directory that
    moves never hits.
    """
    import jax

    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", float(min_compile_secs)
    )
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
