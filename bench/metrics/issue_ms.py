"""Device milliseconds per outer iteration and chip in the issue half:
the instructions whose innermost scope is ``sodda.issue`` (the sample draw,
the snapshot gradient's passes over X). See ``bench/scopes.py``."""
from bench import scopes


def read(ctx):
    return scopes.read(ctx, "issue")
