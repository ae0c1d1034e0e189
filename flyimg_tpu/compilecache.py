"""Where JAX's persistent compilation cache lives.

One rule for every entry point (``service/app.py``, ``bench.py``,
``chip_smoke.py``, the benchmark scripts): where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and no directory is set in code; where it is
not, the cache is ``<checkout>/var/cache/xla``, resolved from this package's
location. The directory is part of what makes a later process find the
entries again, so it never depends on the current directory, a temp name, a
pid or the time.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = "var/cache/xla"  # the compilation_cache_dir knob's default


def compile_cache_dir(configured: Optional[str] = DEFAULT_DIR) -> Optional[str]:
    """The directory the compile cache will use, or None when disabled.
    Imports no JAX. ``configured`` is the ``compilation_cache_dir`` knob:
    a relative path resolves against the checkout, ``''`` disables; the
    environment variable wins over either."""
    env = os.environ.get(_ENV)
    if env:
        return env
    if not configured:
        return None
    return os.path.join(_CHECKOUT, configured)


def enable_compile_cache(configured: Optional[str] = DEFAULT_DIR) -> Optional[str]:
    """Point JAX's persistent compilation cache at ``compile_cache_dir``
    and return the directory in use (None when disabled or unwritable —
    an unwritable location must not turn an optimization into a boot
    failure)."""
    import jax

    path = compile_cache_dir(configured)
    if path is None:
        return None
    if not os.environ.get(_ENV):
        try:
            os.makedirs(path, exist_ok=True)
        except OSError as exc:
            logging.getLogger(__name__).warning(
                "compilation cache disabled (%s unwritable: %s)", path, exc
            )
            return None
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, however quickly it compiled: a threshold makes
    # "did the second start compile anything" depend on compile-time noise
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
