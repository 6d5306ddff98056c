"""Where XLA's persistent compilation cache lives.

One rule, shared by ``fedml_tpu.init()`` and ``chip_smoke.py``:
the directory is placed from OUTSIDE when ``JAX_COMPILATION_CACHE_DIR`` is
set (jax reads the variable itself, so nothing is set in code), and is
otherwise one fixed, git-ignored directory inside the checkout. The path is
part of what makes a cache reusable between runs, so it is never derived
from a temp dir, a pid or the clock.

A process pinned to the CPU platform gets no cache: the cache exists for the
chip's minutes-long compiles, and the CPU test tier must leave nothing in
the checkout.

What a warm run still compiles: only the directory is chosen here. jax
persists a program only when it took a second or more to compile
(``jax_persistent_cache_min_compile_time_secs``), so a warm run loads
those and compiles every sub-second program again — most of the programs
by count, little of the time. Whoever places the cache from outside can
lower that threshold the same way
(``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0``).

The in-checkout default is the package's parent directory, which is the
checkout because the package runs from a source tree (there is no install
step); an installed copy must be given ``JAX_COMPILATION_CACHE_DIR``.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def _pinned_to_cpu() -> bool:
    """True when the platform list (``JAX_PLATFORMS`` / ``jax_platforms``)
    starts with cpu. Reads configuration only — no backend is initialised."""
    platforms = jax.config.jax_platforms or ""
    return platforms.split(",")[0].strip().lower() == "cpu"


def configure_compile_cache() -> Optional[str]:
    """Call before the first compile. Returns the directory this call set,
    or None when it set nothing (placed from outside, or CPU-pinned)."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR") or _pinned_to_cpu():
        return None
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
