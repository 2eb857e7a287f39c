"""1 - (the union of the device ops' intervals over the traced window),
averaged over the cell's chips."""


def read(ctx):
    tr = ctx["trace"]
    if tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
