"""The work a fit requires, counted from shapes: the floor no implementation
can go under, whatever it does.

Bytes are float32 reads of X (4 per element) plus the inner chains' rows and
vectors. An outer iteration must read the rows of D^t in the columns of
B^t once (C^t lies inside B^t, so one fused pass gives both products); at a
record tick the objective reads all of X, and the same pass serves that
iteration's issue half, since both read the same iterate; the final
objective reads X once more. So a fit of K iterations recording every r
reads

    4 N M [ (K - k) db + k + 1 ],  k = ceil(K / r) ticks below K,

with db the sampled share of rows times that of columns. FLOPs count one
multiply-add as two: 2 |D| |B| + 2 |D| |C| per iteration for the snapshot
gradient (``repro.core.sodda.iteration_flops``'s arithmetic, copied), 2 N M
per objective, and 6 mt L per chain for the inner loop.
"""
from __future__ import annotations

import json
import os
from typing import Dict

BYTES = 4  # float32


def _sizes(config: dict):
    P, Q, N, M, L = (int(config[k]) for k in ("P", "Q", "N", "M", "L"))
    n, mt = N // P, M // (P * Q)
    b = max(1, int(round(config["b_frac"] * M)))
    c = max(1, min(b, int(round(config["c_frac"] * M))))
    d = max(1, int(round(config["d_frac"] * n)))
    return P, Q, N, M, L, n, mt, b, c, d


def ticks_below(iters: int, record_every: int) -> int:
    return len(range(0, iters, record_every))


def inner_call(config: dict, chains: int) -> Dict[str, float]:
    """FLOPs and bytes of one inner-kernel call over `chains` chains: rows
    Xl and labels yl read, w0 and mu read, wL written."""
    _, _, _, _, L, _, mt, _, _, _ = _sizes(config)
    return {"flops": 6.0 * chains * L * mt,
            "bytes": float(BYTES * chains * (L * mt + L + 3 * mt))}


def fit_work(config: dict, iters: int, record_every: int) -> Dict[str, float]:
    """FLOPs and bytes one fit requires, over all its chips."""
    P, Q, N, M, L, n, mt, b, c, d = _sizes(config)
    k = ticks_below(iters, record_every)
    share = (d / n) * (b / M)
    passes = (iters - k) * share + k + 1
    inner = inner_call(config, P * Q)
    flops = (iters * (2.0 * P * d * b + 2.0 * P * d * c + inner["flops"])
             + (k + 1) * 2.0 * N * M)
    return {"flops": flops,
            "bytes": BYTES * N * M * passes + iters * inner["bytes"]}


def least_seconds(work: Dict[str, float], peak: Dict[str, float],
                  chips: int) -> float:
    """The larger of FLOPs over peak FLOP/s and bytes over peak HBM B/s."""
    return max(work["flops"] / (chips * peak["flops_per_s"]),
               work["bytes"] / (chips * peak["hbm_bytes_per_s"]))


def peaks(device_kind: str) -> Dict[str, float]:
    """The row of ``peaks.json`` for `device_kind`; KeyError if absent."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; have "
                       f"{sorted(table)}")
    return table[device_kind]
