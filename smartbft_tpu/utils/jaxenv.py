"""JAX platform and compile-cache helpers.

Two ways to run the program:

* **On the chip** nothing here pins a platform: JAX picks the TPU and
  fails at start-up if it cannot.  One process owns a chip at a time, so
  a launcher that starts children which need the chip stays off JAX
  itself.
* **On the CPU** (tests, standalone drive scripts) call :func:`force_cpu`
  before the first JAX backend initialization (first ``jnp`` op /
  ``jax.devices()``), ideally right after ``import jax``.

Both paths share one compile-cache rule, :func:`enable_compile_cache`.
"""

from __future__ import annotations

import hashlib
import os
import re

#: the fixed, git-ignored cache root inside the checkout (the path is part
#: of the cache key, so a directory that moves never hits)
_CACHE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def cache_dir() -> str:
    """The in-checkout compile-cache dir, keyed by a machine fingerprint.

    XLA:CPU stores AOT-compiled code keyed only by the computation; loading
    a cache entry compiled on a host with different CPU features (the
    driver's machine vs this one) emits `cpu_aot_loader.cc` feature-mismatch
    warnings and can SIGILL mid-suite.  Keying the directory by the host's
    CPU-flags hash confines each cache to machines that can execute it.
    """
    src = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                # x86 spells it 'flags'; aarch64 spells it 'Features'
                if line.startswith(("flags", "Features")):
                    src = line
                    break
    except OSError:
        pass
    if not src:  # no /proc (macOS) or unrecognized format
        import platform

        src = f"{platform.machine()}-{platform.processor()}"
    tag = hashlib.sha256(src.encode()).hexdigest()[:12]
    return os.path.join(_CACHE_ROOT, tag)


def enable_compile_cache() -> None:
    """Turn on jax's persistent compilation cache.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and
    this sets nothing; otherwise the cache lives at :func:`cache_dir`."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", cache_dir())


def force_cpu(virtual_devices: int | None = None) -> None:
    """Pin this process to the CPU backend.

    ``virtual_devices``: optionally fake an N-device host platform
    (``--xla_force_host_platform_device_count``) for Mesh/sharding tests.
    A smaller pre-existing count in XLA_FLAGS is raised to the requested
    one (a larger one is kept — extra devices never hurt).  Only effective
    if jax hasn't initialized yet.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    if virtual_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
        if m and int(m.group(1)) < virtual_devices:
            flags = flags.replace(
                m.group(0),
                f"--xla_force_host_platform_device_count={virtual_devices}",
            )
            os.environ["XLA_FLAGS"] = flags
        elif not m:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={virtual_devices}"
            ).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")
    enable_compile_cache()
