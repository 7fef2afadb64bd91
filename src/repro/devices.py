"""Device identity and the persistent compile cache for entry points.

Library code never calls these on import: a script, a benchmark or an
example calls ``enable_compile_cache()`` once at start-up, and labels
what it prints with ``device_label()``.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

# <checkout>/.jax_cache: a fixed path, so a later run finds what an
# earlier one compiled (the path is part of the cache key)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def compile_cache_dir(backend: str) -> Optional[Path]:
    """Where this process should put its compile cache: None when
    ``JAX_COMPILATION_CACHE_DIR`` is set (JAX reads it itself) or on the
    CPU (its compiles are cheap, and jax 0.9 warns on reloading them),
    else the checkout's fixed ``.jax_cache``."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR") or backend == "cpu":
        return None
    return DEFAULT_CACHE_DIR


def enable_compile_cache() -> Optional[Path]:
    """Turn on JAX's persistent compile cache for an accelerator; returns
    the directory set here (None when the environment chose it, or on the
    CPU)."""
    path = compile_cache_dir(jax.default_backend())
    if path is not None:
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path


def pallas_interpret() -> bool:
    """Pallas kernels compile natively on a TPU and are interpreted on
    every other platform."""
    return jax.default_backend() != "tpu"


def device_info() -> dict:
    """Platform, kind and count of the devices JAX runs on."""
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def device_label() -> str:
    """``platform/kind xcount``, for lines that report times or counts."""
    d = device_info()
    return f"{d['platform']}/{d['kind']} x{d['count']}"
