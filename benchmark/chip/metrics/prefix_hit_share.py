"""Share of the window's admissions that found their prompt in the
prompt-entry cache (pool_stats() prefix_hits over hits + misses).
Layer: serving scheduler; moves ttft_ms_p95."""


def read(obs):
    c = obs["counters"]
    n = c.get("prefix_hits", 0) + c.get("prefix_misses", 0)
    if not n:
        return None
    return 100.0 * c["prefix_hits"] / n
