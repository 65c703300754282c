"""Seconds inside the backend compiler during set-up (JAX monitoring
event ``backend_compile_duration``, summed). Moves ``setup_s``."""


def read(ctx):
    return ctx["setup_compile"]["compile_s"]
