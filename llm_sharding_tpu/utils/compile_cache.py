"""Where compiled programs are kept between processes.

The serving programs take tens of seconds each to compile for a chip; a
daemon restart or a second run should reload them instead. JAX's persistent
compilation cache does that, and its directory is part of the cache key —
a directory that moves never hits. The contract:

- ``JAX_COMPILATION_CACHE_DIR`` set: the operator (or the harness that
  starts this program) placed the cache. JAX reads that variable by
  itself; this module sets nothing.
- unset, TPU backend: one fixed directory inside the checkout
  (``DEFAULT_CACHE_DIR``; git-ignored) — never ``~``, a temp name, a pid or
  a time, so every process of every run of this checkout shares it. A
  directory that cannot be created is an error, not a silent cold start.
- unset, any other backend: no cache of this program's own. XLA:CPU
  executables are pinned to the machine that built them, and a new process
  reloading them can hang or crash at deserialization.

Wherever the cache lives, its key includes the programs' metadata
(``jax_compilation_cache_include_metadata_in_key``; JAX leaves it out by
default). The metadata is what a profiler trace names device work by — the
``jax.named_scope`` paths of ``obs.stepline.SCOPES``, file and line — and an
executable loaded from the cache keeps the metadata it was COMPILED with:
with the default key, a commit that only moves a scope is served the old
names by a cache an earlier commit filled, and every metric read from scopes
goes quietly wrong. The price is one recompile after an edit that shifts
the lines of a traced function; a restart of the same code still hits.

Call before the first compilation; the CLI does at entry.
"""

from __future__ import annotations

import os
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: <checkout>/.jax_cache — the parent of the package directory is the
#: checkout root when the program runs from its repository.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_persistent_cache(platform: str) -> Optional[str]:
    """Apply the contract above for a process whose backend is ``platform``
    (``jax.devices()[0].platform``). An argument, not a probe made here:
    the caller decides when the backend may be initialised — the ``worker``
    path only after ``jax.distributed.initialize``. Returns the directory
    in use, or None when no cache is on."""
    placed = os.environ.get(ENV_VAR)
    if not placed and platform != "tpu":
        return None
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if placed:
        return placed
    os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)  # unusable → OSError
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
