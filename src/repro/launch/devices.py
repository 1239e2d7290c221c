"""Device-side process setup: fake host devices for CPU multi-device runs,
and the persistent compilation cache.

jax locks the device count at first backend initialization, so the
``--xla_force_host_platform_device_count`` XLA flag must be set before any
device query. Every consumer (the dry-run driver, the distributed tests,
``benchmarks/dist_bench``) routes through :func:`fake_devices`, which either
sets the flag in time or fails with an actionable error — replacing the
import-time ``os.environ`` mutation that used to live in ``launch/dryrun.py``.
"""

from __future__ import annotations

import os
import pathlib
import re
import sys

_FLAG = "--xla_force_host_platform_device_count"
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (gitignored): this file is src/repro/launch/devices.py
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def _declared_count() -> int:
    """The fake-device count currently requested via XLA_FLAGS (1 if unset)."""
    m = re.search(rf"{_FLAG}=(\d+)", os.environ.get("XLA_FLAGS", ""))
    return int(m.group(1)) if m else 1


def _backend_initialized() -> bool:
    jx = sys.modules.get("jax")
    if jx is None:
        return False
    try:
        from jax._src import xla_bridge

        return xla_bridge.backends_are_initialized()
    except (ImportError, AttributeError):  # pragma: no cover - old/new jax
        return True  # cannot tell: be conservative, refuse to mutate


def fake_devices(n: int) -> int:
    """Ensure this process sees ``n`` (fake) host devices; returns ``n``.

    Idempotent when the flag already requests ``n``. Raises ``RuntimeError``
    with a clear fix when jax has already initialized its backends with a
    different count — env mutation after that point is silently ignored by
    jax, which is exactly the failure mode this helper exists to surface.
    """
    if n < 1:
        raise ValueError(f"device count must be >= 1, got {n}")
    if _backend_initialized():
        import jax

        have = len(jax.devices())
        if have == n:
            return n
        raise RuntimeError(
            f"jax is already initialized with {have} device(s); cannot "
            f"switch to {n}. Call repro.launch.fake_devices({n}) before any "
            f"jax device query, or set XLA_FLAGS={_FLAG}={n} in the "
            f"environment before starting python.")
    if _declared_count() == n:
        return n  # flag already requests n; nothing to rewrite
    flags = os.environ.get("XLA_FLAGS", "")
    if re.search(rf"{_FLAG}=\d+", flags):
        flags = re.sub(rf"{_FLAG}=\d+", f"{_FLAG}={n}", flags)
    else:
        flags = f"{flags} {_FLAG}={n}".strip()
    os.environ["XLA_FLAGS"] = flags
    return n


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    return it: ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads the
    variable itself, so nothing is set here), else ``<checkout>/.jax_cache``.
    The path never holds a temporary directory, a pid or a time, so a later
    process in the same checkout finds what this one compiled. Call before
    the first compile; every launcher and ``chip_smoke.py`` does."""
    path = os.environ.get(CACHE_DIR_ENV)
    if path:
        return path
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
