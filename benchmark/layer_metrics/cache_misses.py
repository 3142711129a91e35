"""Persistent compile-cache misses during set-up, counted by the program's
hook on JAX's cache (`compile_cache.persistent_cache_events`); what the
checks after the window compile is not in it. Every program of a run
after a checkout's first should be in the cache."""


def read(ctx):
    return ctx["compiles"].get("cache_misses")
