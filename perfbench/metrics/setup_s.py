"""Seconds from process start to the window: JAX start, building the cell,
and one warm-up dispatch that compiles the cell's program or loads it from
the persistent cache (host clock)."""


def read(run):
    return run.setup_s
