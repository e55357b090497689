"""Where this checkout keeps what it caches between runs — ONE rule.

Three caches persist across processes: jax's persistent compilation
cache, the serving tier's AOT executables (serving_aot.py) and the
autotuner's decisions (ops/autotune.py). All three live under one
fixed, git-ignored directory inside the checkout (`.veles_cache/`),
never under the home directory and never under a name built from a pid,
a temporary name or the time: the directory is part of the compilation
cache's key, so a cache that moves never hits. Each can be moved from
outside: `JAX_COMPILATION_CACHE_DIR` (jax reads it itself — nothing in
this repo sets a directory in code when it is present),
`VELES_SERVING_AOT_CACHE`, `VELES_AUTOTUNE_CACHE`.
"""

from __future__ import annotations

import os

#: the one fixed cache root: <checkout>/.veles_cache (listed in .gitignore)
CACHE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".veles_cache")


def cache_path(*parts: str) -> str:
    return os.path.join(CACHE_ROOT, *parts)


def enable_compilation_cache() -> str:
    """Turn on jax's persistent compilation cache and return the
    directory in force. The environment places it; absent that, the
    fixed in-checkout directory. First AlexNet compile is tens of
    seconds; later launches in the same place hit the cache (parity
    slot: the reference's on-disk kernel-binary cache, SURVEY.md §2.2).
    Touches jax.config only — no backend is initialised. Every entry
    point passes here, so this is also where the process starts to count
    jax's compile stages and the cache's hits and misses
    (`telemetry/compile_stages.py`)."""
    import jax

    from veles_tpu.telemetry import compile_stages
    compile_stages.listen()
    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not directory:
        directory = cache_path("xla")
        os.makedirs(directory, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return directory
