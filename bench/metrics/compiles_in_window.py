"""Executables built or loaded while the window ran (``jax.monitoring``'s
backend-compile events, cache hits included). Should be 0: every shape the
window uses is warmed up in set-up."""


def read(ctx):
    return float(ctx["compiles"])
