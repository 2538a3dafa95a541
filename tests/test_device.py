"""The device helper: interpret-mode choice and compile-cache placement."""

import os

import jax

from repro import device


def test_interpret_kernels_only_off_tpu(monkeypatch):
    assert jax.default_backend() == "cpu"
    assert device.interpret_kernels()
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    assert not device.interpret_kernels()


def _restoring_cache_config(fn):
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        return fn()
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


def test_compile_cache_defaults_inside_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)

    def run():
        path = device.enable_compile_cache()
        return path, jax.config.jax_compilation_cache_dir, \
            jax.config.jax_persistent_cache_min_compile_time_secs
    path, configured, min_secs = _restoring_cache_config(run)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == configured == os.path.join(repo, ".jax_cache")
    assert min_secs == device.CACHE_MIN_COMPILE_SECS < 1.0


def test_compile_cache_honours_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    def run():
        before = jax.config.jax_compilation_cache_dir
        return device.enable_compile_cache(), before, \
            jax.config.jax_compilation_cache_dir
    path, before, after = _restoring_cache_config(run)
    assert path == str(tmp_path)
    assert after == before          # no other directory set in code
