"""Persistent-cache hits JAX reported before the window: every program
a warm run did not have to compile. Layer: entry and compile cache;
moves setup_s."""


def read(obs):
    return obs["counters"].get("cache_hits_at_setup")
