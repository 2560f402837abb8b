"""kaminpar_tpu — TPU-native balanced k-way graph partitioning.

A brand-new JAX/XLA framework with the capabilities of KaHIP/KaMinPar
(deep multilevel partitioning: LP coarsening, pool bipartitioning,
LP/JET/balancer refinement), designed TPU-first per SURVEY.md.
"""

__version__ = "0.1.0"

import os as _os

import jax as _jax

#: Root of the checkout this package lives in.
CHECKOUT_DIR = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))

# Persistent XLA compilation cache: multilevel runs hit a bounded set of
# shape-ladder kernel shapes (graph/csr.py PaddedView), so caching them on
# disk makes every run after the first start warm.  The cache directory is a
# deployment setting with one owner: JAX_COMPILATION_CACHE_DIR when it is
# set (jax reads it itself), else <checkout>/.xla_cache.  Nothing else in the
# package sets a cache directory.  AOT executable caching stays off: jaxlib's
# CPU executable serializer crashed intermittently while writing entries, and
# cross-machine AOT artifacts reload with machine-feature mismatches.
_jax.config.update("jax_persistent_cache_enable_xla_caches", "none")
_jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
_jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update(
        "jax_compilation_cache_dir", _os.path.join(CHECKOUT_DIR, ".xla_cache")
    )

from .context import Context, PartitioningMode  # noqa: E402
from .kaminpar import KaMinPar  # noqa: E402
from .presets import (  # noqa: E402
    create_context_by_preset_name,
    create_default_context,
)

__all__ = [
    "Context",
    "KaMinPar",
    "PartitioningMode",
    "create_context_by_preset_name",
    "create_default_context",
]
