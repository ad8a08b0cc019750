"""Small shared helpers with no heavier home."""

from __future__ import annotations

import logging
import os
from typing import Sequence

logger = logging.getLogger(__name__)

#: Where compiled programs persist when the environment does not say:
#: ``<checkout>/.jax_cache``, from this package's own location. The
#: directory must not move between runs (never tempfile, a pid or the
#: clock): a cache that moves never hits.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; every entry point calls
    this first (transport/cli.py serving commands). With
    ``JAX_COMPILATION_CACHE_DIR`` set JAX reads it itself and nothing is
    set in code — that is how the cache is moved; otherwise it lives at
    :data:`COMPILE_CACHE_DIR`. Returns the directory in use. A 7B program
    takes tens of seconds to compile and a fresh process otherwise starts
    with none."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax

        path = COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    logger.info("compile cache at %s", path)
    return path


def bucket(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket >= n; beyond the largest bucket, round up to a
    multiple of it (bounded compile count) instead of silently truncating —
    any hard cap (model context, cache length) is applied by callers. The
    single bucketing policy for prompt lengths (servers/llmserver.py) and
    detector window counts (analytics/outliers.py)."""
    for b in buckets:
        if n <= b:
            return b
    top = buckets[-1]
    return ((n + top - 1) // top) * top
