"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 1,2,3 [--fits 7] [--out readings.jsonl]

In one process, at the cell's own size and through the window's own
program: for every seed, fits 1..k of the program against the float32
reference (the lower readings); for each control seed, the same fits
replayed in the program's place by two controls against the float32
reference (the upper readings): ``control``, the reference computed in
bfloat16, and ``x_bf16``, the float32 reference reading X stored in
bfloat16 (the step that halves the bytes of this byte-bound program). One
JSON line per fit, one per (seed, kind) with the run's numbers as
``check.summarize`` gives them (every number, held by the cell or not) and
``check.judge``'s verdict under the cell's limits, then a summary line with
the largest program reading and the smallest control reading of each
number. Benchmark runs never run
this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import run as bench_run  # noqa: E402
from bench import check, spec  # noqa: E402


def readings(cell: spec.Cell, seeds, control_seeds, fits: int, out,
             require_tpu: bool = True):
    """Yield one reading per (seed, fit, kind); `out` gets each as a line."""
    import jax.numpy as jnp
    import numpy as np

    from bench import reference

    devices = bench_run.devices_for(cell, require_tpu)
    if devices is None:
        raise SystemExit(1)
    bench = bench_run.Fits(cell, devices)
    lower = {"control": dict(dtype=jnp.bfloat16),
             "x_bf16": dict(store=jnp.bfloat16)}
    limits = cell.limits["limits"]
    for seed in seeds:
        bench.make_data(seed)
        outs = [bench.fit(i) for i in range(1, fits + 1)]
        tiles = reference.Tiles(bench.X, bench.y)
        refs = []
        for i, (w, hist) in enumerate(outs, 1):
            t = time.perf_counter()
            refs.append(bench.reference_fit(tiles, i))
            rows = [("program", i, hist, np.asarray(w),
                     time.perf_counter() - t)]
            yield from _emit(seed, rows, refs, out)
        del tiles
        yield _verdict(seed, "program", refs, [(w, h) for w, h in outs],
                       limits, out)
        for kind, how in (lower.items() if seed in control_seeds else ()):
            tiles = reference.Tiles(bench.X, bench.y, **how)
            rows = [(kind, i) + bench.reference_fit(tiles, i) + (None,)
                    for i in range(1, fits + 1)]
            del tiles
            yield from _emit(seed, rows, refs, out)
            yield _verdict(seed, kind, refs, [(r[3], r[2]) for r in rows],
                           limits, out)


def _verdict(seed, kind, refs, outs, limits, out):
    """The run's numbers over one seed's fits, and whether they pass."""
    per_fit = [check.readings(hist, w, *ref)
               for (w, hist), ref in zip(outs, refs)]
    numbers = check.summarize(per_fit)
    ok, _ = check.judge(numbers, limits)
    row = {"seed": seed, "kind": kind, "run": True, "correct": ok,
           **numbers}
    print(json.dumps(row), file=out, flush=True)
    return row


def _emit(seed, rows, refs, out):
    for kind, i, hist, w, ref_s in rows:
        ref_hist, ref_w = refs[i - 1]
        row = {"seed": seed, "fit": i, "kind": kind, "reference_s": ref_s,
               **check.readings(hist, w, ref_hist, ref_w)}
        print(json.dumps(row), file=out, flush=True)
        yield row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--fits", type=int, default=0,
                    help="fits per seed; default: the cell's check_fits")
    ap.add_argument("--out", default="-")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",")}
    fits = args.fits or int(cell.limits["check_fits"])
    bench_run.setup_jax()
    out = sys.stdout if args.out == "-" else open(args.out, "w")
    try:
        rows = list(readings(cell, seeds, control, fits, out))
    finally:
        if out is not sys.stdout:
            out.close()
    runs = [r for r in rows if r.get("run")]
    summary = {"workload": cell.name, "limits": cell.limits["limits"],
               "correct": {k: [r["correct"] for r in runs if r["kind"] == k]
                           for k in ("program", "control", "x_bf16")}}
    for name in check.NAMES:
        summary[name] = {"program_max": max(r[name] for r in runs
                                            if r["kind"] == "program")}
        for kind in ("control", "x_bf16"):
            summary[name][f"{kind}_min"] = min(
                (r[name] for r in runs if r["kind"] == kind), default=None)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
