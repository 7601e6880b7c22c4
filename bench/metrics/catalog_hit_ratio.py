"""Memory Catalog hits over hits and misses of the window's rounds, from
the program's ``catalog_hits`` and ``catalog_misses`` counters, in
percent."""


def read(obs):
    hits = obs.counters.get("catalog_hits", 0.0)
    total = hits + obs.counters.get("catalog_misses", 0.0)
    return 100.0 * hits / total if total else None
