"""Process set-up shared by the entry points: ``chip_smoke.py``,
``examples/`` and ``benchmarks/``.

Call ``start()`` first thing in an entry point's ``main()`` — never at
import time, since tests and worker processes import these modules. It

  - keeps JAX's persistent compile cache in ``$JAX_COMPILATION_CACHE_DIR``
    when that variable is set (JAX reads it itself; nothing else is set
    in code), and in ``<checkout>/.jax_cache`` otherwise — a fixed path,
    because the cache key includes it;
  - chooses the platform once. A TPU is used when JAX finds one. The CPU
    route — Pallas interpret mode and the XLA/numpy stand-ins keyed on
    ``kernels.common.default_interpret`` — is taken only when
    ``JAX_PLATFORMS`` asks for the CPU explicitly. Anything else raises:
    a missing chip is never replaced by the CPU in silence.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def check_platform(platform: str, jax_platforms: str | None) -> str:
    """The platform decision as a pure function of what JAX found and of
    ``$JAX_PLATFORMS``: "tpu" or an explicitly requested "cpu"."""
    if platform == "tpu":
        return platform
    asked = {p.strip() for p in (jax_platforms or "").split(",")}
    if platform == "cpu" and "cpu" in asked:
        return platform
    raise RuntimeError(
        f"no TPU found (JAX backend {platform!r}); set JAX_PLATFORMS=cpu "
        "to run on the CPU in Pallas interpret mode on purpose")


def start() -> jax.Device:
    """Compile cache on, platform checked; returns the first device."""
    use_compile_cache()
    dev = jax.devices()[0]
    check_platform(dev.platform, os.environ.get("JAX_PLATFORMS"))
    return dev
