"""Where JAX's persistent compilation cache lives.

Every entry point that compiles for the chip (``chip_smoke.py``,
``benchmark/run.py``, the example trainers) calls
:func:`use_compile_cache` first, so that a second process — or a second
call on a machine that keeps its disk — loads the step program instead of
compiling it again.  The directory is part of the cache key, so it must not
move between runs: it is the one ``JAX_COMPILATION_CACHE_DIR`` names, or
else one fixed path inside the checkout.  Tests leave the cache off.
"""

from __future__ import annotations

import os
from typing import Optional

__all__ = ["use_compile_cache", "DEFAULT_CACHE_DIR"]

# <checkout>/.jax_cache (listed in .gitignore)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def use_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on for a run on an accelerator.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set the directory is JAX's to
    read from the environment and none is set in code (returns None);
    otherwise the cache goes to :data:`DEFAULT_CACHE_DIR` (returned).
    Either way every program is kept, however short its compile (JAX's own
    threshold is a second): a serve engine warms some twenty small programs,
    embed, head and commit a prefill rung, each of which the chip's compiler
    takes 0.2-0.6 s to build and 0.05 s to load (PERF.md §6, PR 32).  On
    the CPU backend nothing is set (returns None): an XLA:CPU executable is
    tied to the CPU features of the machine that built it, and reloading
    one elsewhere warns at best."""
    import jax

    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
