"""`window_compiles` (count; layer: fused dispatch; program counter):
compilations between the window's opening and its close, served by the
persistent cache or not (`compilecache.process_cache_counts`).
Expected 0.  Moves `cand_per_s`."""


def read(obs):
    return obs.get("window_compiles")
