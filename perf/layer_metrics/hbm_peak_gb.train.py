"""memory_stats()["peak_bytes_in_use"], max over the cell's chips, GB."""


def read(obs):
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in obs["devices"])
    return peak / 1e9 if peak else None
