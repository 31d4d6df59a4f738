"""Share of the window's admissions that came in through the radix
tier (a memoised generation replayed over shared blocks). Layer:
serving scheduler; moves ttft_ms_p95."""


def read(obs):
    c = obs["counters"]
    n = c.get("prefix_hits", 0) + c.get("prefix_misses", 0)
    if not n:
        return None
    return 100.0 * c.get("radix_admissions", 0) / n
