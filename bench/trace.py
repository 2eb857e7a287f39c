"""Reduce a profiler trace of the measured window to device times.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes: the ops of
each TPU's "XLA Ops" line (a while loop's event encloses its body's ops),
the collectives of its "Async XLA Ops" line, and the harness's own host
spans (``fit.init``, ``fit.dispatch``, ``fit.fetch``), all on the
profiler's common clock. Op names are cut to the HLO instruction's name
(``%fusion.12 = f32[...] fusion(...)`` becomes ``fusion.12``).

``reduce`` clips the device ops to the window the host spans cover and
gives, averaged over the chips, the time in which some op ran (the union of
the intervals); each op name's self time (its time less that of the ops
nested in it), summed over chips; the inner kernel's time and calls; the
collectives' time (the union of their intervals on both lines); and the
longest idle gaps, each named by the host span in force at its midpoint.
"""
from __future__ import annotations

import collections
import glob
import os
from typing import Dict, List, Tuple

Event = Tuple[str, float, float]  # name, start ns, end ns

HOST_SPANS = ("fit.init", "fit.dispatch", "fit.fetch")
COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
               "reduce-scatter", "all-to-all")
OPS_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"


def find_xplane(profile_dir: str) -> str:
    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"want one .xplane.pb under {profile_dir}, "
                                f"found {paths}")
    return paths[0]


def op_name(name: str) -> str:
    """``%name = type op(...)`` -> ``name``; other names as they are."""
    return name.split(" = ", 1)[0].lstrip("%") if " = " in name else name


def _is_collective(name: str) -> bool:
    return any(c in name for c in COLLECTIVES)


def load(path: str) -> Dict[str, object]:
    """{"devices": {plane: [Event]}, "async": {plane: [Event]},
    "host": [Event]} from an xplane file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, asyncs, host = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name not in (OPS_LINE, ASYNC_LINE):
                    continue
                events = [(op_name(e.name), e.start_ns,
                           e.start_ns + e.duration_ns) for e in line.events]
                if line.name == OPS_LINE:
                    devices[plane.name] = events
                else:
                    asyncs[plane.name] = [e for e in events
                                          if _is_collective(e[0])]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events if e.name in HOST_SPANS)
    return {"devices": devices, "async": asyncs,
            "host": sorted(host, key=lambda e: e[1])}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _self_times(events: List[Event]) -> List[Tuple[str, float]]:
    """(name, self ns) of each event: its length less its direct children's
    (events that start and end inside it, one line of nesting deeper)."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    own = [e[2] - e[1] for e in order]
    stack = []  # indices into order
    for i, (_, a, b) in enumerate(order):
        while stack and order[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            own[stack[-1]] -= b - a
        stack.append(i)
    return [(e[0], t) for e, t in zip(order, own)]


def _span_at(host: List[Event], t: float) -> str:
    """The innermost host span covering time t, or "host" if none does."""
    best = None
    for name, a, b in host:
        if a <= t <= b and (best is None or b - a < best[1]):
            best = (name, b - a)
    return best[0] if best else "host"


def reduce(trace: Dict[str, object], kernel: str = "sodda_inner",
           top: int = 10) -> Dict[str, object]:
    host, devices = trace["host"], trace["devices"]
    if not host or not devices:
        raise ValueError("trace holds no host spans or no TPU ops")
    w0, w1 = min(e[1] for e in host), max(e[2] for e in host)

    def clip(events):
        return [(n, max(a, w0), min(b, w1)) for n, a, b in events
                if min(b, w1) > max(a, w0)]

    op_s = collections.Counter()
    busy, kernel_s, kernel_calls, coll_s, gaps = [], 0.0, 0, 0.0, []
    for plane, events in devices.items():
        events = clip(events)
        for name, ns in _self_times(events):
            op_s[name] += ns * 1e-9
        kernel_ev = [(a, b) for n, a, b in events if kernel in n]
        kernel_s += sum(b - a for a, b in kernel_ev) * 1e-9
        kernel_calls += len(kernel_ev)
        coll = [(a, b) for n, a, b in events + clip(
            trace.get("async", {}).get(plane, [])) if _is_collective(n)]
        coll_s += sum(b - a for a, b in _union(coll)) * 1e-9
        merged = _union([(a, b) for _, a, b in events])
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, _span_at(host, (a + b) / 2)))
    chips = len(devices)
    gaps.sort(reverse=True)
    return {
        "chips": chips,
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / chips,
        "op_s": dict(op_s),
        "kernel_s": kernel_s,
        "kernel_calls": kernel_calls,
        "collective_s": coll_s,
        "device_ops": [[n, s] for n, s in op_s.most_common(top)],
        "idle_gaps": [[n, s * 1e-9] for s, n in gaps[:top]],
    }
