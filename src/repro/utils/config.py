"""One-call process configuration for jax: platform, x64, host devices.

jax reads ``XLA_FLAGS`` and most of ``jax.config`` exactly once — when
the backend is first initialised (the first ``jax.devices()`` /
``jnp.asarray`` / jit trace).  Setting them later silently does nothing
(or raises deep inside XLA), which is how "works on my machine, single
device in CI" bugs are born.  :func:`configure` centralises the dance:
call it once at process start, *before anything touches jax*, and it
either applies the settings or fails loudly explaining why it cannot.

Typical entry-point usage::

    from repro.utils.config import configure
    configure(platform="cpu", x64=False, host_devices=8)
    import jax  # safe either way; jax must not be *initialised* yet
"""

from __future__ import annotations

import os
import sys
from typing import Optional

__all__ = ["configure", "enable_compile_cache", "jax_is_initialized",
           "host_device_count"]

_DEVICE_FLAG = "--xla_force_host_platform_device_count"
# The checkout root (src/repro/utils/config.py -> three levels up).
_CHECKOUT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))


def jax_is_initialized() -> bool:
    """True if jax has already created a backend (config is frozen)."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge
    return xla_bridge.backends_are_initialized()


def host_device_count() -> Optional[int]:
    """The ``--xla_force_host_platform_device_count`` currently in
    ``XLA_FLAGS``, or None if the flag is absent."""
    flags = os.environ.get("XLA_FLAGS", "")
    for tok in flags.split():
        if tok.startswith(_DEVICE_FLAG + "="):
            try:
                return int(tok.split("=", 1)[1])
            except ValueError:
                return None
    return None


def _set_device_flag(n: int) -> None:
    flags = [tok for tok in os.environ.get("XLA_FLAGS", "").split()
             if not tok.startswith(_DEVICE_FLAG + "=")]
    flags.append(f"{_DEVICE_FLAG}={int(n)}")
    os.environ["XLA_FLAGS"] = " ".join(flags)


def configure(platform: Optional[str] = None,
              x64: Optional[bool] = None,
              host_devices: Optional[int] = None) -> None:
    """Configure the jax runtime for this process, before first use.

    Parameters
    ----------
    platform:
        "cpu", "gpu", or "tpu" — pins ``jax_platform_name`` so the
        process cannot silently fall back to a different backend.
    x64:
        Flip the *global* default float width.  Prefer the scoped
        ``jax.enable_x64(True)`` context inside library code; the
        global switch is for benchmark / CLI entry points that own the
        whole process.
    host_devices:
        Present ``N`` fake host devices on CPU via
        ``--xla_force_host_platform_device_count=N`` so shard_map /
        mesh code paths run multi-device on machines without
        accelerators.

    Raises
    ------
    RuntimeError
        If jax has already initialised its backends — at that point
        ``host_devices`` / ``platform`` cannot take effect, and
        failing loudly beats a simulator that silently runs on one
        device.
    """
    if platform is None and x64 is None and host_devices is None:
        return
    if jax_is_initialized():
        if host_devices is not None and host_device_count() == host_devices:
            # Idempotent re-call with the same topology: harmless.
            host_devices = None
        if host_devices is not None or platform is not None:
            raise RuntimeError(
                "repro.utils.config.configure() called after jax was "
                "initialised — XLA_FLAGS/platform changes can no longer "
                "take effect. Call configure() at process start, before "
                "importing modules that build jax arrays.")

    if host_devices is not None:
        if host_devices < 1:
            raise ValueError(f"host_devices must be >= 1, got {host_devices}")
        _set_device_flag(host_devices)

    import jax  # deferred: XLA_FLAGS must be in the env first

    if platform is not None:
        jax.config.update("jax_platform_name", platform)
    if x64 is not None:
        jax.config.update("jax_enable_x64", bool(x64))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``, so a second run in the same checkout
    finds what the first compiled (a path derived from a temp name, pid
    or time would start empty every run). Call it before the first
    compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
