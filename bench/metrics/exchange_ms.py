"""Device milliseconds per outer iteration and chip in the exchange: the
instructions whose innermost scope is ``sodda.exchange``, the mesh step's
psums and all-gathers. Nothing on one chip, where no step has a collective.
See ``bench/scopes.py``."""
from bench import scopes


def read(ctx):
    return scopes.read(ctx, "exchange")
