"""Roofline-driven autotuning for the SODDA inner Pallas kernel.

The kernel (`sodda_inner.py`) tiles the L dimension by `BlockConfig.block_l`
and streams `(block_l, mt)` X tiles through double-buffered VMEM. This
module owns the schedule side of that contract:

* **Legality** — a config is legal iff `block_l` divides L, the kernel's
  mt is lane-aligned (multiple of 128; `ops.sodda_inner` pads before the
  kernel sees it), and the per-program VMEM footprint, padded to Mosaic's
  (8, 128) tiles, fits the budget. The kernel's 4-D blocks span their
  arrays' last two dimensions, so Mosaic's block-shape rule holds for every
  divisor of L; everything the TPU compiler refuses is refused here first
  (compiled against a described v5e in tests/test_tpu_compile.py).
  Illegal configs are refused with the named errors `AlignmentError` /
  `VmemBudgetError` (both `KernelTuningError`), never silently clamped.
* **Scoring** — `predicted_time_s` prices each legal config with the
  `launch/roofline.py` machine model (PEAK_FLOPS / HBM_BW) plus a
  per-grid-step dispatch term: a single tile loads everything before
  compute starts (`t_compute + t_memory`), a tiled chain overlaps the
  streamed loads with compute (`max(t_compute, t_memory)` + the first
  tile's un-hidden fill) at the cost of per-tile overhead. The model's
  honest conclusion for this memory-bound kernel: the largest block that
  fits VMEM wins, and tiling is what keeps big (L, mt) shapes legal at
  all — which is exactly when it pays.
* **Determinism** — `autotune` is a pure function of
  (loss, L, mt, platform) plus any cached measured timings: candidates
  are enumerated in a fixed order, ties break toward larger `block_l`,
  and the winner is cached in-memory and (optionally) on disk as the
  config's `as_dict` form, so repeated calls — and separate processes
  sharing a cache dir — select identically.
* **Measured refinement** — pass `measure=` (a callable
  `BlockConfig -> seconds`) to re-rank the model's top candidates with
  real timings when a compiled (non-interpret) path exists. The default
  config is always in the measured set, so the winner never regresses it.

Run ``python -m repro.kernels.tuning --loss hinge --L 64 --mt 512`` for
the CI perf-smoke selection report.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Callable, Dict, Optional, Tuple

from repro import platform as repro_platform
from repro.launch import roofline

LANE = 128  # TPU lane width: the kernel's mt axis must align to this
SUBLANE = 8  # f32 rows per VMEM tile
VMEM_BYTES = 16 * 2 ** 20  # Mosaic's default scoped VMEM limit on v5e
# Fraction of VMEM the kernel may plan for; the rest is headroom for
# compiler temporaries and semaphores.
VMEM_BUDGET = int(VMEM_BYTES * 0.75)

# Modeled per-grid-step scheduling overhead (seconds). TPU grid steps are
# pipelined (near-free); interpret mode pays a Python-level walk per step,
# which is why the model never tiles on cpu/interpret platforms.
DISPATCH_OVERHEAD_S = {"tpu": 5e-8, "gpu": 2e-7, "cpu": 5e-5}

# Candidates the measured-refinement pass re-ranks (model's top-k).
MEASURE_TOP_K = 3


class KernelTuningError(ValueError):
    """Base class for refused kernel configurations."""


class AlignmentError(KernelTuningError):
    """block_l does not divide L, or mt is not lane-aligned."""


class VmemBudgetError(KernelTuningError):
    """The config's per-program VMEM footprint exceeds the budget."""


@dataclasses.dataclass(frozen=True)
class BlockConfig:
    """Tunable schedule of `sodda_inner_pallas`: rows per L-tile."""

    block_l: int

    def as_dict(self) -> dict:
        return {"block_l": int(self.block_l)}

    @classmethod
    def from_dict(cls, d: dict) -> "BlockConfig":
        return cls(block_l=int(d["block_l"]))


def padded_mt(mt: int) -> int:
    """mt after `ops.sodda_inner`'s zero-padding to the lane width."""
    return mt + (-mt) % LANE


def vmem_bytes(config: BlockConfig, L: int, mt: int) -> int:
    """Per-program VMEM plan for `config` on an (L, mt) block (f32).

    Mosaic stores every VMEM buffer in (8, 128) tiles, so rows round up to
    the sublane count and a ``(block_l, 1)`` column takes a full lane
    tile. Double-buffered streams (X tile + label column; Pallas overlaps
    the next tile's copy with this tile's compute) + the double-buffered
    ``(1, mt)`` w0/mu/out blocks (out carries the running wbar) + the
    per-tile ``d0`` margin column scratch.
    """
    mtp = padded_mt(mt)
    rows = config.block_l + (-config.block_l) % SUBLANE
    x_stream = 2 * rows * mtp * 4
    y_stream = 2 * rows * LANE * 4
    resident = 3 * 2 * SUBLANE * mtp * 4
    margins = rows * LANE * 4
    return x_stream + y_stream + resident + margins


def validate_config(config: BlockConfig, L: int, mt: int,
                    vmem_limit: int = VMEM_BUDGET) -> None:
    """Raise a named `KernelTuningError` unless `config` is legal."""
    bl = config.block_l
    if bl < 1 or bl != int(bl):
        raise AlignmentError(f"block_l={bl!r} is not a positive integer")
    if L % bl != 0:
        raise AlignmentError(
            f"block_l={bl} does not divide L={L}; partial tiles would "
            "change the chain order")
    if mt % LANE != 0:
        raise AlignmentError(
            f"mt={mt} is not a multiple of the {LANE}-lane width; "
            "ops.sodda_inner pads before the kernel — pass the padded mt")
    need = vmem_bytes(config, L, mt)
    if need > vmem_limit:
        raise VmemBudgetError(
            f"block_l={bl} needs {need} B of VMEM for (L={L}, mt={mt}), "
            f"budget is {vmem_limit} B — use a smaller block_l")


def default_config(L: int, mt: int) -> BlockConfig:
    """The seed kernel's schedule: one tile spanning all of L."""
    return BlockConfig(block_l=L)


def legal_configs(L: int, mt: int,
                  vmem_limit: int = VMEM_BUDGET) -> Tuple[BlockConfig, ...]:
    """Every legal config for (L, mt), largest block_l first.

    Enumeration order is fixed (descending divisors of L) so downstream
    selection is deterministic.
    """
    mtp = padded_mt(mt)
    out = []
    for bl in range(L, 0, -1):
        if L % bl:
            continue
        cfg = BlockConfig(block_l=bl)
        try:
            validate_config(cfg, L, mtp, vmem_limit)
        except KernelTuningError:
            continue
        out.append(cfg)
    return tuple(out)


def predicted_time_s(config: BlockConfig, L: int, mt: int,
                     platform: str = "tpu") -> float:
    """Modeled seconds for one (p, q) block's chain under `config`.

    Uses the roofline constants: ~8 flops per (row, coordinate) — the
    hoisted matvec (2) plus the chain's dot/axpy work (6) — against
    PEAK_FLOPS, and the block's HBM traffic against HBM_BW. A single
    tile serializes load and compute; a tiled chain overlaps them but
    pays the first tile's fill plus per-tile overhead.
    """
    mtp = padded_mt(mt)
    n_tiles = L // config.block_l
    flops = 8.0 * L * mtp
    hbm = 4.0 * (L * mtp + L + 3 * mtp)  # X + y streamed; w0/mu in, out back
    t_compute = flops / roofline.PEAK_FLOPS
    t_memory = hbm / roofline.HBM_BW
    overhead = n_tiles * DISPATCH_OVERHEAD_S.get(platform,
                                                 DISPATCH_OVERHEAD_S["cpu"])
    if n_tiles == 1:
        return t_compute + t_memory + overhead
    tile_fill = 4.0 * (config.block_l * mtp + config.block_l) / roofline.HBM_BW
    return max(t_compute, t_memory) + tile_fill + overhead


# ---------------------------------------------------------------------------
# Selection + caching

_CACHE: Dict[str, BlockConfig] = {}
_CACHE_FILE = "sodda_tuning_cache.json"


def _cache_key(loss: str, L: int, mt: int, platform: str) -> str:
    return f"loss={loss}|L={L}|mt={padded_mt(mt)}|platform={platform}"


def clear_cache() -> None:
    _CACHE.clear()


def _disk_cache_path(cache_dir: str) -> str:
    return os.path.join(cache_dir, _CACHE_FILE)


def _disk_load(cache_dir: str) -> dict:
    path = _disk_cache_path(cache_dir)
    if not os.path.exists(path):
        return {}
    with open(path) as fh:
        return json.load(fh)


def _disk_store(cache_dir: str, key: str, config: BlockConfig) -> None:
    os.makedirs(cache_dir, exist_ok=True)
    payload = _disk_load(cache_dir)
    payload[key] = config.as_dict()
    path = _disk_cache_path(cache_dir)
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(payload, fh, indent=2, sort_keys=True)
    os.replace(tmp, path)


def autotune(loss: str, L: int, mt: int, platform: Optional[str] = None,
             cache_dir: Optional[str] = None,
             measure: Optional[Callable[[BlockConfig], float]] = None,
             ) -> BlockConfig:
    """Pick the `BlockConfig` for (loss, L, mt, platform). Deterministic.

    Selection: model score (`predicted_time_s`) over `legal_configs`,
    ties toward larger block_l (the fixed enumeration order). With
    `measure`, the model's top `MEASURE_TOP_K` candidates are re-ranked
    by measured seconds (model score is the tie-break). The winner is
    cached under (loss, L, padded mt, platform) — in memory always, and
    in `cache_dir/sodda_tuning_cache.json` when a dir is given — so the
    choice round-trips deterministically across calls and processes.
    """
    if platform is None:
        platform = repro_platform.platform()
    key = _cache_key(loss, L, mt, platform)
    if key in _CACHE:
        return _CACHE[key]
    if cache_dir is not None:
        stored = _disk_load(cache_dir).get(key)
        if stored is not None:
            config = BlockConfig.from_dict(stored)
            _CACHE[key] = config
            return config

    candidates = legal_configs(L, padded_mt(mt))
    if not candidates:
        raise VmemBudgetError(
            f"no legal BlockConfig for (L={L}, mt={mt}) under "
            f"{VMEM_BUDGET} B of VMEM")
    scored = sorted(
        candidates,
        key=lambda c: (predicted_time_s(c, L, mt, platform), -c.block_l))
    winner = scored[0]
    if measure is not None:
        pool = list(scored[:MEASURE_TOP_K])
        default = default_config(L, mt)
        if default in candidates and default not in pool:
            pool.append(default)  # the no-regression anchor
        timed = sorted(
            pool,
            key=lambda c: (measure(c),
                           predicted_time_s(c, L, mt, platform), -c.block_l))
        winner = timed[0]

    _CACHE[key] = winner
    if cache_dir is not None:
        _disk_store(cache_dir, key, winner)
    return winner


def _main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        description="Report the autotuned BlockConfig for a SODDA "
                    "inner-kernel shape (model-only; no device needed).")
    parser.add_argument("--loss", default="hinge")
    parser.add_argument("--L", type=int, default=64)
    parser.add_argument("--mt", type=int, default=512)
    parser.add_argument("--platform", default=None,
                        help="cpu|gpu|tpu (default: the first of "
                             "JAX_PLATFORMS, else cpu)")
    parser.add_argument("--cache-dir", default=None)
    args = parser.parse_args(argv)

    plat = args.platform or repro_platform.declared_platform() or "cpu"
    config = autotune(args.loss, args.L, args.mt, platform=plat,
                      cache_dir=args.cache_dir)
    report = {
        "loss": args.loss, "L": args.L, "mt": args.mt, "platform": plat,
        "selected": config.as_dict(),
        "predicted_us": predicted_time_s(config, args.L, args.mt, plat) * 1e6,
        "candidates": [
            {"block_l": c.block_l,
             "predicted_us": predicted_time_s(c, args.L, args.mt, plat) * 1e6,
             "vmem_bytes": vmem_bytes(c, args.L, padded_mt(args.mt))}
            for c in legal_configs(args.L, padded_mt(args.mt))],
    }
    print(json.dumps(report, indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())
