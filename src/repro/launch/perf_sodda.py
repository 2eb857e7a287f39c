import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=256"
                           " --xla_allow_excess_precision=false")

"""§Perf driver for the paper-representative cell: the doubly-distributed
SODDA outer loop on the production 16x16 mesh (P=16 observation x Q=16
feature partitions), lowered with abstract full-size inputs (dry-run style).

Lowers the *scan-compiled run driver* (``repro.core.driver.make_run``) —
PERF_ITERS fused outer iterations, the program production actually executes
— and reports per-outer-iteration collective bytes / flops per device for
each variant of the update exchange:
  * psum      — zero-padded m-sized delta psum over 'data' (naive)
  * gather    — all_gather of the m_tilde-sized sub-blocks (paper-faithful
                "concatenate", half the wires)
  * gather+q8 — gather deltas + int8-quantized snapshot psum

    PYTHONPATH=src python -m repro.launch.perf_sodda
"""
import jax
import jax.numpy as jnp

from repro.configs.sodda_svm import SoddaConfig
from repro.core import driver
from repro.core.sodda import SoddaState
from repro.launch.roofline import LINK_BW, PEAK_FLOPS, collective_stats, total_link_bytes

PERF_ITERS = 4  # fused outer iterations in the lowered scan program


def analyze(cfg: SoddaConfig, gather: bool, compress: bool,
            compress_z: bool = False):
    from repro.core import engine
    mesh = engine.make_mesh_for(cfg)
    # record_objective=False: lower the pure iteration program — the exact
    # monitoring objective's own collectives are variant-independent and
    # would drown the exchange comparison this table exists for
    run = driver.make_run(cfg, PERF_ITERS, "shard_map",
                          record_every=PERF_ITERS, record_objective=False,
                          mesh=mesh, gather_deltas=gather,
                          compress_mu=compress, compress_z=compress_z)
    X = jax.ShapeDtypeStruct((cfg.N, cfg.M), jnp.float32)
    y = jax.ShapeDtypeStruct((cfg.N,), jnp.float32)
    state = SoddaState(
        w=jax.ShapeDtypeStruct((cfg.M,), jnp.float32),
        t=jax.ShapeDtypeStruct((), jnp.int32),
        key=jax.ShapeDtypeStruct((2,), jnp.uint32),
    )
    with mesh:
        comp = run.lower(state, X, y).compile()
    cost = comp.cost_analysis()
    stats = collective_stats(comp.as_text(), cfg.P * cfg.Q)
    # XLA's cost analysis and the HLO text both count the scan body ONCE
    # regardless of trip count, so these are already per-outer-iteration.
    return {
        "flops_per_device": cost.get("flops", 0.0),
        "link_bytes_per_device": total_link_bytes(stats),
        "per_kind": {k: round(v["link_bytes"] / 1e3, 1)
                     for k, v in stats.items() if v["count"]},
        "t_compute_us": cost.get("flops", 0.0) / PEAK_FLOPS * 1e6,
        "t_collective_us": total_link_bytes(stats) / LINK_BW * 1e6,
    }


def main():
    from repro import platform as repro_platform

    # latency-hiding XLA flags for the analyzed collectives (no-op on cpu);
    # must precede the first jax backend touch below
    repro_platform.configure()
    # production-scale GLM: 16x16 grid, 2M observations x 64k features
    cfg = SoddaConfig(P=16, Q=16, n=131072, m=4096, L=256)
    print(f"SODDA perf cell: N={cfg.N} M={cfg.M} grid 16x16, L={cfg.L}, "
          f"(b,c,d)=({cfg.b_frac},{cfg.c_frac},{cfg.d_frac})")
    out = {}
    for name, (g, c, cz) in {
        "psum": (False, False, False),
        "gather": (True, False, False),
        "gather+q8mu": (True, True, False),
        "gather+q8z": (True, True, True),
    }.items():
        r = analyze(cfg, g, c, cz)
        out[name] = r
        print(f"{name:10s} link_bytes/dev={r['link_bytes_per_device']/1e3:10.1f}KB "
              f"t_coll={r['t_collective_us']:8.2f}us "
              f"t_comp={r['t_compute_us']:8.2f}us  per_kind={r['per_kind']}")
    base = out["psum"]["link_bytes_per_device"]
    for name in ("gather", "gather+q8mu", "gather+q8z"):
        print(f"{name}: collective bytes vs psum baseline: "
              f"{out[name]['link_bytes_per_device']/base:.3f}x")
    # data-parallel SGD reference: full-gradient all-reduce every inner step
    dp = 2 * 15 / 16 * cfg.M * 4 * cfg.L
    print(f"reference: data-parallel SGD moving {dp/1e3:.1f}KB per outer "
          f"iteration (L={cfg.L} inner steps x full-M all-reduce)")
    return out


if __name__ == "__main__":
    main()
