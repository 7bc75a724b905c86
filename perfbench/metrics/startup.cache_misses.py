"""Persistent-compile-cache misses during set-up (jax.monitoring events):
0 once every program of the cell is in the cache."""


def read(run):
    return run["facts"].get("setup_cache_misses")
