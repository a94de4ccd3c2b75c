"""TPU query execution engine (ref: pinot-core query engine, SURVEY.md 2.4).

The per-segment Filter -> Projection -> Transform -> Aggregation chain runs
as fused masked vector ops under jax.jit (kernels.py), planned per query
structure (plan.py), with host paths for selection/distinct/fallback
(host_engine.py) and reduce-side merging (results.py).
"""

def ensure_x64() -> None:
    """Enable 64-bit jax types for exact OLAP semantics (reference aggregates
    in double/long). Called at executor/session setup — not at import — so
    importing this package does not flip process-global jax config. On TPU
    f64/i64 are emulated (f32-pairs); metadata-driven narrowing to f32/i32 is
    the planned optimization for the hot kernels."""
    import jax

    jax.config.update("jax_enable_x64", True)


def ensure_compile_cache() -> None:
    """Keep compiled programs across processes (JAX's persistent
    compilation cache). Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX
    reads it itself and no directory is set in code; otherwise the cache
    lives at ``<checkout>/.jax_cache`` — a fixed path, because the path is
    part of how a deployment finds its cache again. Every compile is
    kept, however short: a cold start is hundreds of sub-second star-tree
    and index kernels, one per pow2-padded capacity."""
    import os

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        checkout = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(checkout, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


from pinot_tpu.engine.errors import QueryError, UnsupportedQueryError
from pinot_tpu.engine.executor import ServerQueryExecutor
from pinot_tpu.engine.residency import QueryLease, ResidencyManager
from pinot_tpu.engine.results import DataSchema, QueryStats, ResultTable

__all__ = [
    "QueryError",
    "UnsupportedQueryError",
    "ServerQueryExecutor",
    "ResidencyManager",
    "QueryLease",
    "DataSchema",
    "QueryStats",
    "ResultTable",
]
