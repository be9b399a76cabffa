"""One place that decides where XLA's persistent compilation cache lives.

The directory is part of nothing's identity but its own: a cache that moves
never hits, so it is either where the operator put it
(``JAX_COMPILATION_CACHE_DIR``, which jax reads itself — nothing is set in
code then) or the fixed ``<checkout>/.jax_cache`` (git-ignored).  Never a
temporary, pid- or time-derived path.  Every runner, bench and tool calls
:func:`setup_compile_cache` before its first compile.
"""

from __future__ import annotations

import logging
import os

__all__ = ["DEFAULT_CACHE_DIR", "setup_compile_cache"]

_logger = logging.getLogger(__name__)

#: ``<checkout>/.jax_cache`` — the checkout is the directory holding the
#: ``deepfake_detection_tpu`` package
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def setup_compile_cache(cli_dir: str = "",
                        min_compile_secs: float = 0.0) -> str:
    """Turn the persistent cache on; returns the directory in effect.

    Precedence: ``JAX_COMPILATION_CACHE_DIR`` (left to jax — no directory
    is set in code), then ``cli_dir`` (a runner's ``--compile-cache-dir``),
    then :data:`DEFAULT_CACHE_DIR`.  The entry-size floor is dropped so
    small serving programs persist; ``min_compile_secs`` keeps a suite's
    hundreds of trivial programs out.
    """
    import jax
    where = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if where:
        if cli_dir and os.path.abspath(cli_dir) != os.path.abspath(where):
            _logger.warning("--compile-cache-dir %s ignored: "
                            "JAX_COMPILATION_CACHE_DIR=%s wins", cli_dir,
                            where)
    else:
        where = os.path.abspath(cli_dir or DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    return where
