"""Fusion engine and jit: XLA compiles inside the measured window (JAX's
compile events). Set-up warms every shape the window uses, so this reads 0
unless a shape or a static argument escaped the warm-up."""


def read(window):
    return window.compiles
