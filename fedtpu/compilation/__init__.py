"""AOT compilation subsystem: persist, key and overlap XLA compilation.

Three layers (ISSUE 3):

- :mod:`fedtpu.compilation.cache` — ``ProgramCache``, a content-addressed
  store of serialized executables with integrity/version guards, plus
  ``configure_persistent_cache`` for jax's backend compilation cache;
- :mod:`fedtpu.compilation.executor` — ``CompileExecutor``, a background
  compile thread pool that builds not-yet-needed programs while the
  current one runs;
- :mod:`fedtpu.compilation.warmup` — ``warmup_preset``, the ``fedtpu
  warmup`` driver pre-compiling a preset's program family into a cache
  directory.

Import-light: jax loads only when a compile/lookup actually happens.
"""

from fedtpu.compilation.cache import (CACHE_DIR_ENV, CACHE_FORMAT_VERSION,
                                      DEFAULT_CACHE_DIR, PROGRAMS_SUBDIR,
                                      CacheEntry, ProgramCache,
                                      configure_persistent_cache,
                                      environment_fingerprint,
                                      program_cache_dir, program_fingerprint,
                                      resolve_cache_dir)
from fedtpu.compilation.executor import CompileExecutor
from fedtpu.compilation.warmup import program_config_slice, warmup_preset

__all__ = [
    "CACHE_DIR_ENV",
    "CACHE_FORMAT_VERSION",
    "CacheEntry",
    "CompileExecutor",
    "DEFAULT_CACHE_DIR",
    "PROGRAMS_SUBDIR",
    "ProgramCache",
    "configure_persistent_cache",
    "environment_fingerprint",
    "program_cache_dir",
    "program_config_slice",
    "program_fingerprint",
    "resolve_cache_dir",
    "warmup_preset",
]
