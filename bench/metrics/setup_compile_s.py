"""Seconds of set-up spent compiling or reading compiled programs from
the persistent cache: JAX's backend-compile duration events up to the
window's start (each holds its cache read, if any)."""


def read(ctx):
    return ctx.setup_compile_s
