"""Device milliseconds per outer iteration and chip outside every
``sodda.*`` scope: the per-fit layout copy of X, loop control, the history's
assembly. See ``bench/scopes.py``."""
from bench import scopes


def read(ctx):
    return scopes.read(ctx, "unscoped")
