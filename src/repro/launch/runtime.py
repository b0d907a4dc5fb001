"""Process set-up shared by the entry points: device count, compile cache.

Importing this module does not import JAX: ``force_host_devices`` has to
run before the first JAX import of the process.
"""

from __future__ import annotations

import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
))))
CACHE_DIR = os.path.join(REPO, ".jax_cache")


def force_host_devices(n: int):
    """Emulate ``n`` CPU devices (``--devices``); a no-op for ``n == 0``.

    Only the CPU backend honours the flag; :func:`check_devices` rejects a
    request an accelerator backend cannot meet."""
    if n:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}"
        )


def check_devices(n: int):
    """Fail when an accelerator backend has fewer than ``n`` devices."""
    import jax

    have = len(jax.devices())
    if n and jax.default_backend() != "cpu" and have < n:
        raise SystemExit(
            f"--devices {n}: the {jax.default_backend()} backend has only "
            f"{have} device(s); --devices emulates devices on the CPU only"
        )


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and no
    directory is set here. Otherwise the cache lives at the fixed
    ``<repo>/.jax_cache``, so every run from this checkout finds what the
    earlier ones compiled.

    The cache key includes the programs' metadata: by default JAX strips
    it, and two programs that differ only in their ``op_name``s (the layer
    scopes of ``energy/trace.region``) would share an entry, so one would
    load the other's executable and profile under its names."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
