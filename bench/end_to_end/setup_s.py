"""Seconds from the process's start to the measured window: imports, JAX's
start, the campaign's data, and one warm-up campaign at the cell's shapes
(compiles, or loads from the persistent cache, every program)."""


def read(window):
    return window.setup_s
