"""The one place that points JAX's persistent compilation cache somewhere.

Entry points (``cli/train.py``, ``bench.py``, ``chip_smoke.py``) call
:func:`enable` before their first compile. Library code that tests
construct never calls it on its own: the CPU suite stays off a persistent
cache on purpose (``tests/conftest.py``).
"""

from __future__ import annotations

import os
from typing import Optional

#: ``<checkout>/.jax_cache`` — fixed, because a cache directory that moves
#: between runs never hits. Git-ignored.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def enable(path: Optional[str] = None) -> str:
    """Turn the persistent compile cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set: JAX reads it itself and
    no directory is set here. Otherwise the cache goes to ``path``
    (``--compile_cache_dir``) or :data:`DEFAULT_DIR`.

    Either way the cache's key holds the program's metadata. JAX strips debug
    info from a key by default, and a ``named_scope`` is debug info: two trees
    whose steps differ in scope names alone would share one entry, and the
    second would be served the first's executable with the first's
    ``op_name``s, which ``obs/hlo_scopes.py`` reads. The price: moving a
    traced source line is a new key, so such a tree compiles cold once.
    """
    import jax  # noqa: PLC0415 — entry points parse args before importing jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = path or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    # keep the sub-second programs too (init, eager ops): on the v5e a warm
    # smoke run still spent 39 s compiling under the default 1 s threshold,
    # 7 s without it (PR 21 chip runs)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
