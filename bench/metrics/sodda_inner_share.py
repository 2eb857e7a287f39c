"""Device time of the ``sodda_inner`` calls over the device's busy time,
both summed over the cell's chips."""


def read(ctx):
    tr = ctx["trace"]
    if not tr["kernel_calls"] or tr["busy_s"] <= 0:
        return None
    return 100.0 * tr["kernel_s"] / (tr["busy_s"] * tr["chips"])
