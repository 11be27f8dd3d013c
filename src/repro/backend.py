"""What the process runs on: Pallas interpret mode and the compile cache.

`interpret_mode` is decided once, from the default backend: Pallas kernels
run interpreted on a CPU host (tests, CI) and compiled on a TPU.  No other
backend has kernels, and none silently falls back to the interpreter.

`enable_compilation_cache` turns on JAX's persistent compilation cache for
the entry points (chip smoke, launchers, benchmarks).  The path is part of
the cache key, so it is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads it itself), else ``.jax_cache/`` at the
root of the checkout.
"""

from __future__ import annotations

import functools
import os
import pathlib

import jax

#: In-checkout cache directory used when the environment names none.
CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


@functools.cache
def interpret_mode() -> bool:
    """True on a CPU backend (Pallas interpret mode), False on TPU."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernels for backend {backend!r}: the "
                       "kernels compile for TPU and run interpreted on CPU")


def enable_compilation_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        path = env
    else:
        CACHE_DIR.mkdir(exist_ok=True)
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def device_label() -> dict:
    """The device this process computes on, as JAX reports it."""
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices()), "interpret": interpret_mode()}
