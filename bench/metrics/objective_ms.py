"""Device milliseconds per outer iteration and chip recording the
objective: the instructions whose innermost scope is ``sodda.objective``.
Reads 0.0 where the scope labels instructions of the program but every
objective op that ran was fused into another stage's op. See
``bench/scopes.py``."""
from bench import scopes


def read(ctx):
    return scopes.read(ctx, "objective")
