"""Where JAX's persistent compile cache sits."""

from __future__ import annotations

import os


def place(root: str, config: dict) -> str:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` if set,
    else at one fixed path inside the checkout (the program's own choice:
    ``<checkout>/.jax_cache``). The program raises the cache's minimum
    compile time to 0.5 s in code; the benchmark lowers it to 0 after
    that (the configuration file's ``jax`` settings), so that the second
    run of a cell finds every program."""
    import jax

    import geomesa_tpu

    geomesa_tpu.enable_compile_cache()
    for name, value in config.get("jax", {}).items():
        jax.config.update(name, value)
    path = jax.config.jax_compilation_cache_dir
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path or (env and path != env) or (not env and not path.startswith(root)):
        raise RuntimeError(f"compile cache at {path!r}, expected inside {root!r} or at {env!r}")
    return path
