"""The Pallas inner kernel's share of its roofline: for every
``sodda_inner`` call in the trace, the larger of its FLOPs (6 L mt per
chain) over peak FLOP/s and its bytes (rows, labels, w0, mu read, wL
written) over peak HBM bytes/s, summed, over the summed device time of
those calls. Nothing when the trace holds no such call."""
from bench import work


def read(ctx):
    tr = ctx["trace"]
    if not tr["kernel_calls"] or tr["kernel_s"] <= 0:
        return None
    cfg = ctx["config"]
    chains = int(cfg["P"]) * int(cfg["Q"]) // ctx["chips"]
    least = work.least_seconds(work.inner_call(cfg, chains), ctx["peaks"], 1)
    return 100.0 * tr["kernel_calls"] * least / tr["kernel_s"]
