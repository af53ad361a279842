"""Persistent XLA compilation cache, placed from outside.

A cold compile of the flagship train step costs tens of seconds on a TPU
and every process pays it again.  Entry points (``chip_smoke.py``, the
example trainers) call :func:`enable_compile_cache`
before their first jit so a second run of the same program on the same
machine loads instead of compiling.

The directory is part of the cache key's lookup, so it never moves:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; no directory is
  set in code (the operator owns the location);
- unset: ``<checkout>/.jax_cache`` (git-ignored) — fixed per checkout,
  never a temp dir, pid or timestamp.
"""

from __future__ import annotations

import contextlib
import os
from typing import Iterator, Optional

from torchft_tpu.utils.env import env_str

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on for this process.

    Returns the directory this call configured, or ``None`` when
    ``JAX_COMPILATION_CACHE_DIR`` already names one.  Every compile is
    stored (JAX's default skips programs that compile in under a second,
    which would drop the Pallas kernels and the per-leaf optimizer ops).
    """
    import jax

    path: Optional[str] = None
    if not env_str("JAX_COMPILATION_CACHE_DIR"):
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


@contextlib.contextmanager
def compile_cache_disabled() -> Iterator[None]:
    """Compile (and load) without the persistent cache inside the block.

    Two kinds of program must stay out of it:

    - AOT compiles for a DESCRIBED topology (no chip attached): the entry
      is written but can never be read back;
    - multi-device programs for a device subset that does not start at the
      process's first device (threads-as-replica-groups on disjoint
      sub-meshes).  On jax 0.9.0 / libtpu 0.0.34 such an executable, once
      DESERIALIZED from the cache, halts the TensorCore at its first launch
      ("Invalid logical z: enhanced-barrier-parent-phase"); compiled fresh
      it runs.  One process per slice never builds such a mesh.

    Process-wide (JAX's switch is global): not for use while other threads
    compile.
    """
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()  # the used/unused decision is memoised
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
