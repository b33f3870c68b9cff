"""Where JAX keeps its persistent compilation cache for this repo's programs.

Entry points (``chip_smoke.py``, the train and serve CLIs, the DP example,
``bench/run.py``) call :func:`enable_compile_cache` once at
start-up, before their first compile.  Library code and tests never do.
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

# Hashed into every key of the cache.  JAX's key leaves out HLO metadata,
# and with it the op names that the program's ``jax.named_scope``s set and
# that profiler traces are read by: without this, a program that names its
# ops otherwise would load, and trace, an executable named the old way.
# Change it whenever those names change.
OP_NAMES = ("train_forward train_optimizer train_grad_sync pccl_<collective>_<algorithm>/group<i>"
            " encdec_encoder encdec_cross_attention")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this function sets no other directory.  Otherwise the cache lives at
    ``<repo>/.jax_cache`` (git-ignored): a fixed path, so that a later
    process finds the executables an earlier one stored.  Either way the
    keys carry :data:`OP_NAMES`.
    """
    from jax._src import cache_key

    cache_key.custom_hook = lambda: OP_NAMES
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
