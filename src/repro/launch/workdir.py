"""Where a run keeps its local files: inside the checkout, in directories
that git ignores, and nowhere else on the machine."""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]
CKPT_ROOT = CHECKOUT / ".ckpt"


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache; entry points call this
    before their first compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX already reads it and nothing else is set. Otherwise the cache goes
    to the fixed ``<checkout>/.jax_cache``, so a later run from the same
    checkout finds what this one compiled."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(CHECKOUT / ".jax_cache"))
