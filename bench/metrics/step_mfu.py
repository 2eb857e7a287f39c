"""The whole fit's share of the chips' peak: the least time its required
work could take (``bench/work.py``: the larger of FLOPs over peak FLOP/s and
bytes over peak HBM bytes/s) over the wall seconds per fit of the traced
window. The byte bound is the one that binds."""
from bench import work


def read(ctx):
    if not ctx["fits"]:
        return None
    need = work.fit_work(ctx["config"], int(ctx["traffic"]["fit_iters"]),
                         int(ctx["traffic"]["record_every"]))
    least = work.least_seconds(need, ctx["peaks"], ctx["chips"])
    return 100.0 * least / (ctx["window_s"] / ctx["fits"])
