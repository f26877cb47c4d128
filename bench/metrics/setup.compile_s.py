"""setup.compile_s: seconds JAX spent tracing, lowering and compiling (or
loading from the persistent cache) the train step, on the host clock, read
from JAX's own compile events during the warm-up's steps (the first two: the
second step's state arrives committed and gets an executable of its own)."""


def read(ctx):
    return ctx["compile_s"]
