"""Device milliseconds per outer iteration and chip in the consume half:
the instructions whose innermost scope is ``sodda.consume`` (the row gather
and re-layout of X, the inner chains, the assembly). See
``bench/scopes.py``."""
from bench import scopes


def read(ctx):
    return scopes.read(ctx, "consume")
