"""NeedleTail-JAX: LIMIT-query engine reproduction (density maps + any-k)."""
