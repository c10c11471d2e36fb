"""Seconds of backend compilation during set-up: the sum of JAX's
``/jax/core/compile/backend_compile_duration`` events (a program found in
the persistent cache is not compiled and adds nothing)."""


def read(run):
    return run.compile_s
