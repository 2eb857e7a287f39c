"""Seconds to generate and place X and y on the cell's chips, ending in
``block_until_ready``."""


def read(ctx):
    return ctx["data_s"]
