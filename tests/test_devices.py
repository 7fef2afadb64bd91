"""repro.devices: where entry points keep the compile cache, and the
platform's choice of Pallas interpret mode."""
from pathlib import Path

import jax

from repro import devices

REPO = Path(__file__).resolve().parents[1]


def test_environment_cache_dir_wins(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert devices.compile_cache_dir("tpu") is None


def test_default_cache_dir_is_fixed_in_checkout_and_ignored(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = devices.compile_cache_dir("tpu")
    assert path == REPO / ".jax_cache"
    assert path == devices.compile_cache_dir("tpu")  # not per process
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored
    assert devices.compile_cache_dir("cpu") is None


def test_platform_picks_interpret_mode():
    assert devices.pallas_interpret() == (jax.default_backend() != "tpu")
    info = devices.device_info()
    assert info["count"] == len(jax.devices())
    assert devices.device_label().startswith(info["platform"] + "/")
