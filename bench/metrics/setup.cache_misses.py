"""Persistent compile-cache misses during set-up (``jax.monitoring``
events): 0 once the cell's programs are in ``bench/jax-cache``.  Moves
``setup_s``."""


def read(ctx):
    return ctx["setup_events"].get("misses", 0)
