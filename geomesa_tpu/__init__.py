"""geomesa_tpu: a TPU-native spatio-temporal indexing and query framework.

A from-scratch re-design of the capabilities of GeoMesa (reference:
/root/reference, JVM/Scala) for JAX/XLA/Pallas on TPU:

- space-filling-curve indexing (Z2/Z3/XZ2/XZ3) over an HBM-resident,
  Arrow-style columnar feature table sorted by index key,
- a cost-based query planner (filter split -> strategy decision -> ranges),
- push-down filtering and aggregation (density / stats / BIN / sampling)
  executed as vectorized XLA/Pallas scans over contiguous row spans,
- multi-device scale-out via `jax.sharding.Mesh` + collective reductions
  (the analogue of GeoMesa's tablet-server fan-out + client merge).

Architecture inversion (see SURVEY.md section 7): the reference's
row-iterator-over-KV-store becomes columnar-scan-over-HBM. The planner runs
on host (thousands of ops), the scan runs on device (millions of rows).
"""

__version__ = "0.1.0"

import os as _os

_cache_enabled = False


def enable_compile_cache():
    """Persistent XLA compilation cache: scan-kernel shapes are static per
    table, so every process after the first finds its programs on disk
    instead of compiling the kernel ladder again. Called lazily from the
    first device table build (single-device and mesh tables alike, before
    their first compile) - NOT at import, so host-only paths never pay
    the jax import (GEOMESA_TPU_NO_COMPILE_CACHE=1 disables).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already keeps its cache
    there and no directory is set in code; otherwise the cache is
    ``<checkout>/.jax_cache`` and nothing else. The path is part of the
    cache's key, so it never moves."""
    global _cache_enabled
    if _cache_enabled or _os.environ.get("GEOMESA_TPU_NO_COMPILE_CACHE"):
        return
    _cache_enabled = True
    import jax

    if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir",
            _os.path.join(
                _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
                ".jax_cache",
            ),
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


from geomesa_tpu.sft import FeatureType, AttributeDescriptor
from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection

__all__ = [
    "FeatureType",
    "AttributeDescriptor",
    "DataStore",
    "FeatureCollection",
    "__version__",
]
