"""Which device the kernels run on, and where compiled programs are kept.

Every Pallas kernel call site asks :func:`interpret_kernels` whether to
run the kernel through the Pallas interpreter.  The answer depends only
on JAX's default backend: on a TPU the kernels compile for the chip, and
a kernel that the chip's compiler refuses raises — nothing drops to the
interpreter or to the jnp oracle.  Everywhere else (the test suite runs
on the CPU) the interpreter runs them.

:func:`enable_compile_cache` places JAX's persistent compilation cache.
The entry points (``chip_smoke.py``, ``python -m repro.tracks.workflow``)
call it once at start-up.
"""

from __future__ import annotations

import os

import jax

#: Cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is not set: a
#: fixed path inside the checkout (listed in ``.gitignore``), so a later
#: process of the same checkout finds what an earlier one compiled.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

#: Programs that compile faster than this are not written to the cache.
#: Each fused-pipeline bucket and screen shape compiles in about a
#: second, so the whole bucket set lands in the cache.
CACHE_MIN_COMPILE_SECS = 0.1


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def interpret_kernels() -> bool:
    """Run Pallas kernels in interpret mode?  Only off the TPU."""
    return not on_tpu()


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as JAX itself
    reads it, and no other directory is set here.  Otherwise the cache
    goes to :data:`DEFAULT_CACHE_DIR`.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      CACHE_MIN_COMPILE_SECS)
    return path
