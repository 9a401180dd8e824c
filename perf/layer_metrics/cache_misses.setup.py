"""Persistent-compile-cache misses during set-up (0 in a warm run)."""


def read(obs):
    v = obs.get("cache_misses")
    return None if v is None else float(v)
