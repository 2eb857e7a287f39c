"""Tests for the benchmark harness: the async-safe timing helper and the
BENCH_sodda.json schema contract the CI bench-smoke job enforces."""
import copy
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

bench_run = importlib.import_module("benchmarks.run")
validate_bench = importlib.import_module("benchmarks.validate_bench")
bench_trend = importlib.import_module("tools.bench_trend")


# ---------------------------------------------------------------------------
# _t: every rep must be individually blocked. Under jax's async dispatch,
# only syncing the last rep lets earlier calls overlap the timer and
# under-report us/call (the bug this pins).
# ---------------------------------------------------------------------------
def test_t_blocks_every_rep(monkeypatch):
    blocked = []
    real_block = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: blocked.append(x) or real_block(x))
    reps = 4
    us = bench_run._t(lambda a: a + 1.0, jnp.zeros(()), reps=reps)
    assert us > 0
    # warmup + one block per timed rep — not a single trailing block
    assert len(blocked) == reps + 1, (
        f"_t must block_until_ready every rep (got {len(blocked)} blocks "
        f"for {reps} reps + warmup)")


def test_t_returns_mean_us_per_call():
    us = bench_run._t(lambda a: a * 2.0, jnp.ones((8,)), reps=2)
    assert 0 < us < 5e6  # sane microsecond magnitude on any host


# ---------------------------------------------------------------------------
# Driver-bench backend resolution: every registered backend joins (mesh ones
# only when the device grid exists), and a backend that fails to lower on
# the current platform degrades to a WARN row instead of aborting the bench.
# ---------------------------------------------------------------------------
_SPAWNING_CELLS = ("bench_distributed_sodda", "run_large_cell",
                   "run_streaming_cell", "run_multihost_cell",
                   "run_multihost_large_cell", "bench_driver_large",
                   "bench_streaming", "bench_multihost",
                   "bench_multihost_large")


@pytest.mark.parametrize("cell", _SPAWNING_CELLS)
def test_spawning_cells_refuse_on_accelerator(monkeypatch, cell):
    """A cell whose children force CPU host devices refuses, by name,
    before it spawns, when this process holds a chip."""
    import subprocess

    from repro.testing import multiprocess

    def no_spawn(*args, **kwargs):
        raise AssertionError("spawned a child on an accelerator host")

    monkeypatch.setattr(subprocess, "run", no_spawn)
    monkeypatch.setattr(subprocess, "Popen", no_spawn)
    monkeypatch.setattr(multiprocess, "launch_coordinated", no_spawn)
    monkeypatch.setattr(bench_run.jax, "default_backend", lambda: "tpu")
    with pytest.raises(bench_run.AcceleratorHeldError, match=cell):
        getattr(bench_run, cell)()


def test_resolve_driver_backends_covers_registry():
    from repro.core import engine
    from repro.testing import small_fixture_config
    backends, have_mesh = bench_run._resolve_driver_backends(
        small_fixture_config())
    assert backends[0] == "reference"
    assert "async" in backends
    assert set(backends) <= set(engine.available_backends())
    if have_mesh:  # the test session forces 12 devices, so the grid exists
        assert "shard_map" in backends
        assert "async-mesh" in backends
        # the vs-sync comparison cell needs the sync baseline benched first
        assert backends.index("shard_map") < backends.index("async-mesh")
    else:  # no device grid: every mesh backend must drop out, not WARN-fail
        assert not set(backends) & set(engine.MESH_BACKENDS)


def test_bench_driver_warns_not_crashes_on_lowering_failure(
        monkeypatch, tmp_path, capsys):
    from repro.core import engine

    def boom(cfg, opts):
        raise RuntimeError("synthetic lowering failure")

    monkeypatch.setitem(engine._REGISTRY, "zzz-broken", boom)
    monkeypatch.setattr(bench_run, "_resolve_driver_backends",
                        lambda cfg: (["reference", "zzz-broken"], False))
    payload = bench_run.bench_driver(iters=2, reps=1,
                                     out_path=str(tmp_path / "b.json"))
    out = capsys.readouterr().out
    assert "driver_backends_resolved" in out  # the resolved list is printed
    assert "WARN" in out and "zzz-broken" in out
    assert "zzz-broken" not in payload["backends"]
    assert "reference" in payload["backends"]  # later cells still ran


# ---------------------------------------------------------------------------
# BENCH_sodda.json schema (bench_sodda/v1)
# ---------------------------------------------------------------------------
def _valid_payload():
    traj = {"t": [0, 1, 2], "flops": [0.0, 10.0, 20.0],
            "loss": [1.0, 0.8, 0.7]}
    return {
        "schema": "bench_sodda/v1",
        "problem": {"name": "p", "P": 2, "Q": 2, "N": 160, "M": 32,
                    "L": 6, "loss": "hinge"},
        "iters": 2, "reps": 3,
        "backends": {
            "reference": {
                "flops_per_iter": 10.0,
                "python_loop": {"us_per_iter": 9.0,
                                "trajectory": copy.deepcopy(traj)},
                "scan_driver": {"us_per_iter": 3.0,
                                "trajectory": copy.deepcopy(traj)},
                "speedup": 3.0,
            },
        },
    }


def test_schema_accepts_valid_payload():
    assert validate_bench.validate(_valid_payload())


@pytest.mark.parametrize("mutate,match", [
    (lambda p: p.update(schema="bench_sodda/v0"), "schema"),
    (lambda p: p.pop("problem"), "problem"),
    (lambda p: p["problem"].pop("loss"), "problem.loss"),
    (lambda p: p.update(iters=0), "iters"),
    (lambda p: p.update(backends={}), "backends"),
    (lambda p: p["backends"]["reference"].update(flops_per_iter=-1),
     "flops_per_iter"),
    (lambda p: p["backends"]["reference"]["scan_driver"].update(
        us_per_iter=0), "us_per_iter"),
    (lambda p: p["backends"]["reference"]["python_loop"]["trajectory"]
     ["loss"].pop(), "differ in length"),
    (lambda p: p["backends"]["reference"]["scan_driver"]["trajectory"]
     .update(t=[0, 1, 5]), "iters"),
    (lambda p: p["backends"]["reference"].update(speedup=0), "speedup"),
    (lambda p: p["backends"]["reference"]["python_loop"].update(
        loop_iters=5), "loop_iters"),  # > iters
    (lambda p: p["backends"]["reference"].update(
        collective_bytes_per_iter={"z": 1.0}), "collective_bytes"),
    (lambda p: p["backends"]["reference"].update(
        collective_bytes_per_iter={"z": 1.0, "mu": -2.0, "delta": 0.0,
                                   "total": 3.0}), "collective_bytes"),
    (lambda p: p["backends"]["reference"].update(vs_shard_map_us_ratio=0),
     "vs_shard_map_us_ratio"),
])
def test_schema_rejects_violations(mutate, match):
    payload = _valid_payload()
    mutate(payload)
    with pytest.raises(validate_bench.BenchSchemaError, match=match):
        validate_bench.validate(payload)


def test_schema_accepts_mesh_backend_fields():
    """The optional mesh-cell fields (collective bytes, the async-mesh
    vs-sync ratio, the loop timing regime) validate when well-formed."""
    payload = _valid_payload()
    payload["backends"]["reference"]["python_loop"]["loop_iters"] = 2
    payload["backends"]["reference"]["collective_bytes_per_iter"] = {
        "z": 128.0, "mu": 96.0, "delta": 48.0, "total": 272.0}
    payload["backends"]["reference"]["vs_shard_map_us_ratio"] = 1.02
    assert validate_bench.validate(payload)


def test_validate_cli_require_backend(tmp_path, capsys):
    """--require-backend: CI acceptance that the async-mesh cell actually
    made it into the artifact (a host without the device grid would
    silently drop it otherwise)."""
    import json
    path = tmp_path / "b.json"
    path.write_text(json.dumps(_valid_payload()))
    assert validate_bench.main([str(path)]) == 0
    assert validate_bench.main(
        [str(path), "--require-backend", "reference"]) == 0
    assert validate_bench.main(
        [str(path), "--require-backend", "async-mesh"]) == 1
    assert "async-mesh" in capsys.readouterr().out
    assert validate_bench.main([str(path), "--require-backend"]) == 2


def test_bench_driver_preserves_large_problem_block(monkeypatch, tmp_path):
    """Regenerating the per-backend cells must not drop the (separately
    produced, expensive) large_problem block from an existing artifact."""
    import json
    monkeypatch.setattr(bench_run, "_resolve_driver_backends",
                        lambda cfg: (["reference"], False))
    out = tmp_path / "b.json"
    out.write_text(json.dumps({"schema": "bench_sodda/v1",
                               "large_problem": _valid_large_problem()}))
    payload = bench_run.bench_driver(iters=2, reps=1, out_path=str(out))
    assert payload["large_problem"] == _valid_large_problem()
    assert json.loads(out.read_text())["large_problem"] == \
        _valid_large_problem()


def _valid_large_problem():
    return {
        "problem": {"name": "sodda-table1-50kx6k", "P": 5, "Q": 3,
                    "N": 50_000, "M": 6_000, "L": 64, "loss": "hinge"},
        "backend": "shard_map", "plane": "tiled", "iters": 4,
        "us_per_iter": 5e6, "final_loss": 0.4,
        "peak_host_bytes": 4.0e7, "rss_peak_bytes": 3.0e9,
        "dense_xy_bytes": 1.2002e9,
    }


def test_schema_accepts_large_problem_block():
    payload = _valid_payload()
    payload["large_problem"] = _valid_large_problem()
    assert validate_bench.validate(payload)


@pytest.mark.parametrize("mutate,match", [
    (lambda lp: lp.update(plane="dense"), "plane"),
    (lambda lp: lp.update(iters=0), "iters"),
    (lambda lp: lp.update(us_per_iter=0), "us_per_iter"),
    (lambda lp: lp.update(peak_host_bytes=-1), "peak_host_bytes"),
    (lambda lp: lp.pop("final_loss"), "final_loss"),
    (lambda lp: lp["problem"].pop("N"), "problem.N"),
    # the acceptance criterion itself: host staging must undercut dense
    (lambda lp: lp.update(peak_host_bytes=2e9), "below the dense"),
])
def test_schema_rejects_large_problem_violations(mutate, match):
    payload = _valid_payload()
    payload["large_problem"] = _valid_large_problem()
    mutate(payload["large_problem"])
    with pytest.raises(validate_bench.BenchSchemaError, match=match):
        validate_bench.validate(payload)


def _valid_streaming():
    return {
        "problem": {"name": "sodda-stream-20kx2k", "P": 4, "Q": 2,
                    "N": 20_000, "M": 2_000, "L": 32, "loss": "hinge"},
        "backend": "reference", "plane": "streaming",
        "iters": 16, "segment_iters": 4, "epochs": 4,
        "us_per_iter": 2e4, "final_loss": 0.3,
        "prefetch_overlap_ratio": 0.7,
        "prefetch": {"place_s": 1.0, "wait_s": 0.3, "consumed": 4,
                     "cold_misses": 1},
        "cache": {"hits": 10, "misses": 40, "resident": 10},
        "resident_tile_budget": 12,
        "peak_host_bytes": 5.0e7, "rss_peak_bytes": 1.0e9,
        "dense_xy_bytes": 1.6e8, "stream_total_bytes": 6.4e8,
    }


def test_schema_accepts_streaming_block():
    payload = _valid_payload()
    payload["streaming"] = _valid_streaming()
    assert validate_bench.validate(payload)


@pytest.mark.parametrize("mutate,match", [
    (lambda st: st.update(plane="tiled"), "plane"),
    (lambda st: st.update(epochs=1), "epochs"),  # one window is not a stream
    (lambda st: st.update(segment_iters=0), "segment_iters"),
    (lambda st: st.update(prefetch_overlap_ratio=1.5), "overlap"),
    (lambda st: st.update(prefetch_overlap_ratio=-0.1), "overlap"),
    (lambda st: st.pop("final_loss"), "final_loss"),
    (lambda st: st["problem"].pop("M"), "problem.M"),
    # the shipped volume must cover epochs windows
    (lambda st: st.update(stream_total_bytes=1.0e8), "stream_total_bytes"),
    # the out-of-core acceptance criterion: staging undercuts one window
    (lambda st: st.update(peak_host_bytes=2.0e8), "below one dense"),
])
def test_schema_rejects_streaming_violations(mutate, match):
    payload = _valid_payload()
    payload["streaming"] = _valid_streaming()
    mutate(payload["streaming"])
    with pytest.raises(validate_bench.BenchSchemaError, match=match):
        validate_bench.validate(payload)


def test_validate_cli_require_streaming(tmp_path, capsys):
    """--require-streaming: CI acceptance that the streaming cell actually
    materialized (it degrades to a WARN row on hosts that cannot run it)."""
    import json
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps(_valid_payload()))
    assert validate_bench.main([str(bare)]) == 0
    assert validate_bench.main([str(bare), "--require-streaming"]) == 1
    assert "streaming" in capsys.readouterr().out
    full_payload = _valid_payload()
    full_payload["streaming"] = _valid_streaming()
    full = tmp_path / "full.json"
    full.write_text(json.dumps(full_payload))
    assert validate_bench.main([str(full), "--require-streaming"]) == 0


def test_bench_driver_preserves_streaming_block(monkeypatch, tmp_path):
    """Regenerating the per-backend cells must carry the streaming block
    over, exactly like large_problem (the regression this PR fixes for
    separately-produced cells)."""
    import json
    monkeypatch.setattr(bench_run, "_resolve_driver_backends",
                        lambda cfg: (["reference"], False))
    out = tmp_path / "b.json"
    out.write_text(json.dumps({"schema": "bench_sodda/v1",
                               "streaming": _valid_streaming()}))
    payload = bench_run.bench_driver(iters=2, reps=1, out_path=str(out))
    assert payload["streaming"] == _valid_streaming()
    assert json.loads(out.read_text())["streaming"] == _valid_streaming()


def _valid_tuning():
    return {
        "loss": "hinge", "B": 8, "L": 32, "mt": 256, "platform": "cpu",
        "interpret": True,
        "default_config": {"block_l": 32}, "tuned_config": {"block_l": 32},
        "default_us": 100.0, "tuned_us": 100.0,
        "tuned_vs_default_us_ratio": 1.0,
        "legal_block_l": [32, 16, 8, 4, 2, 1],
    }


def test_schema_accepts_tuning_block():
    payload = _valid_payload()
    payload["tuning"] = _valid_tuning()
    assert validate_bench.validate(payload)
    # a genuine tuning win validates too (ratio consistent and < 1)
    payload["tuning"].update(tuned_config={"block_l": 16}, tuned_us=80.0,
                             tuned_vs_default_us_ratio=0.8)
    assert validate_bench.validate(payload)


@pytest.mark.parametrize("mutate,match", [
    # THE acceptance criterion: tuning may never regress the default
    (lambda tn: tn.update(tuned_us=110.0, tuned_vs_default_us_ratio=1.1),
     "<= 1.0"),
    # a ratio that disagrees with the us values it summarizes
    (lambda tn: tn.update(tuned_vs_default_us_ratio=0.5), "not"),
    (lambda tn: tn.update(interpret="yes"), "interpret"),
    (lambda tn: tn.update(B=0), "tuning.B"),
    (lambda tn: tn.update(default_us=0), "default_us"),
    (lambda tn: tn.update(tuned_config={"block_l": 0}), "tuned_config"),
    (lambda tn: tn.pop("loss"), "loss"),
])
def test_schema_rejects_tuning_violations(mutate, match):
    payload = _valid_payload()
    payload["tuning"] = _valid_tuning()
    mutate(payload["tuning"])
    with pytest.raises(validate_bench.BenchSchemaError, match=match):
        validate_bench.validate(payload)


def test_validate_cli_require_tuning(tmp_path, capsys):
    import json
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps(_valid_payload()))
    assert validate_bench.main([str(bare)]) == 0
    assert validate_bench.main([str(bare), "--require-tuning"]) == 1
    assert "tuning" in capsys.readouterr().out
    full_payload = _valid_payload()
    full_payload["tuning"] = _valid_tuning()
    full = tmp_path / "full.json"
    full.write_text(json.dumps(full_payload))
    assert validate_bench.main([str(full), "--require-tuning"]) == 0


def test_validate_cli_help_exits_zero(capsys):
    """The satellite fix: --help used to be opened as an artifact path
    (traceback); it is a successful invocation like in every other CLI."""
    assert validate_bench.main(["--help"]) == 0
    assert "validate_bench" in capsys.readouterr().out  # usage doc printed
    assert validate_bench.main(["-h"]) == 0


# ---------------------------------------------------------------------------
# bench_history/v1: the committed per-PR trajectory.
# ---------------------------------------------------------------------------
def _history_lines(n=2):
    import json
    lines = []
    for i in range(1, n + 1):
        entry = bench_trend.history_entry(_valid_payload(), i, f"PR{i}",
                                          f"2026-08-0{i}")
        lines.append(json.dumps(entry, sort_keys=True))
    return lines


def test_validate_history_accepts_trajectory():
    entries = validate_bench.validate_history("\n".join(_history_lines(3)))
    assert [e["seq"] for e in entries] == [1, 2, 3]


@pytest.mark.parametrize("corrupt,match", [
    (lambda ls: [], "no entries"),
    (lambda ls: ls + ["{not json"], "not valid JSON"),
    (lambda ls: [ls[0].replace("bench_history/v1", "bench_sodda/v1")] +
     ls[1:], "schema"),
    (lambda ls: list(reversed(ls)), "out of order"),
    (lambda ls: [ls[0], ls[0]], "out of order"),  # duplicate seq
    (lambda ls: [ls[0].replace('"PR1"', '""')], "label"),
    (lambda ls: [ls[0].replace('"reference": 3.0', '"reference": 0')],
     "positive"),
])
def test_validate_history_rejects_corruption(corrupt, match):
    lines = corrupt(_history_lines(2))
    with pytest.raises(validate_bench.BenchSchemaError, match=match):
        validate_bench.validate_history("\n".join(lines))


def test_validate_history_bounds_tuning_ratio():
    import json
    entry = bench_trend.history_entry(_valid_payload(), 1, "PR1", "2026-08-01")
    entry["tuning"] = {"tuned_vs_default_us_ratio": 1.2}
    with pytest.raises(validate_bench.BenchSchemaError, match="0, 1"):
        validate_bench.validate_history(json.dumps(entry))
    entry["tuning"] = {"tuned_vs_default_us_ratio": 0.9}
    assert validate_bench.validate_history(json.dumps(entry))


def test_validate_cli_history_mode(tmp_path, capsys):
    good = tmp_path / "h.jsonl"
    good.write_text("\n".join(_history_lines(2)) + "\n")
    assert validate_bench.main(["--history", str(good)]) == 0
    assert "entries=2" in capsys.readouterr().out
    # --history validates a trajectory, not an artifact: the artifact
    # require flags make no sense against it
    assert validate_bench.main(
        ["--history", str(good), "--require-tuning"]) == 2


def test_validate_cli_history_mode_rejects_malformed(tmp_path):
    bad = tmp_path / "h.jsonl"
    lines = _history_lines(2)
    bad.write_text("\n".join(reversed(lines)) + "\n")
    with pytest.raises(validate_bench.BenchSchemaError, match="out of order"):
        validate_bench.main(["--history", str(bad)])


# ---------------------------------------------------------------------------
# tools/bench_trend.py --history: the rolling-best trajectory gate.
# ---------------------------------------------------------------------------
def _write_history(tmp_path, lines, name="h.jsonl"):
    p = tmp_path / name
    p.write_text("\n".join(lines) + ("\n" if lines else ""))
    return str(p)


def test_history_gate_passes_and_catches_regression(tmp_path, capsys):
    h = _write_history(tmp_path, _history_lines(2))
    cur = _valid_payload()  # same numbers as the trajectory: ratio 1.0
    c = _write(tmp_path, "c.json", cur)
    assert bench_trend.main(["--history", h, c, "--threshold", "0.25"]) == 0
    # regress beyond the threshold vs the ROLLING BEST
    cur["backends"]["reference"]["scan_driver"]["us_per_iter"] = 4.5
    c = _write(tmp_path, "c2.json", cur)
    assert bench_trend.main(["--history", h, c, "--threshold", "0.25"]) == 1
    assert "REGRESSED" in capsys.readouterr().out


def test_history_gate_rolling_best_not_latest(tmp_path):
    """A slow latest entry must not mask a regression: the gate compares
    against the best the trajectory ever recorded."""
    import json
    fast = bench_trend.history_entry(_valid_payload(), 1, "PR1", "2026-08-01")
    slow_payload = copy.deepcopy(_valid_payload())
    slow_payload["backends"]["reference"]["scan_driver"]["us_per_iter"] = 9.0
    slow = bench_trend.history_entry(slow_payload, 2, "PR2", "2026-08-02")
    h = _write_history(tmp_path, [json.dumps(fast), json.dumps(slow)])
    cur = _write(tmp_path, "c.json", slow_payload)  # 9.0 vs best 3.0
    assert bench_trend.main(["--history", h, cur,
                             "--threshold", "0.25"]) == 1


def test_history_gate_rejects_malformed_trajectory(tmp_path, capsys):
    c = _write(tmp_path, "c.json", _valid_payload())
    bad = _write_history(tmp_path, _history_lines(1) + ["{broken"])
    assert bench_trend.main(["--history", bad, c]) == 2
    out_of_order = _write_history(tmp_path, list(reversed(_history_lines(2))),
                                  "o.jsonl")
    assert bench_trend.main(["--history", out_of_order, c]) == 2
    assert "ERROR" in capsys.readouterr().out


def test_history_gate_no_comparable_entry(tmp_path, capsys):
    c = _write(tmp_path, "c.json", _valid_payload())
    other = copy.deepcopy(_valid_payload())
    other["iters"] = 99
    import json
    h = _write_history(tmp_path, [json.dumps(
        bench_trend.history_entry(other, 1, "PR1", "2026-08-01"))])
    assert bench_trend.main(["--history", h, c]) == 3
    assert "INCOMPARABLE" in capsys.readouterr().out
    empty = _write_history(tmp_path, [], "e.jsonl")
    assert bench_trend.main(["--history", empty, c]) == 3


def test_history_gate_append_extends_trajectory(tmp_path):
    import json
    h = _write_history(tmp_path, _history_lines(2))
    cur = _valid_payload()
    cur["tuning"] = _valid_tuning()
    c = _write(tmp_path, "c.json", cur)
    assert bench_trend.main(["--history", h, c, "--append",
                             "--label", "PR9", "--date", "2026-08-08"]) == 0
    lines = [ln for ln in open(h).read().splitlines() if ln.strip()]
    assert len(lines) == 3
    tail = json.loads(lines[-1])
    assert tail["seq"] == 3 and tail["label"] == "PR9"
    assert tail["date"] == "2026-08-08"
    assert tail["tuning"] == {"tuned_vs_default_us_ratio": 1.0}
    # the appended trajectory still validates in depth
    assert validate_bench.validate_history(open(h).read())


def test_history_gate_failing_run_does_not_append(tmp_path):
    h = _write_history(tmp_path, _history_lines(2))
    cur = _valid_payload()
    cur["backends"]["reference"]["scan_driver"]["us_per_iter"] = 99.0
    c = _write(tmp_path, "c.json", cur)
    assert bench_trend.main(["--history", h, c, "--append",
                             "--threshold", "0.25"]) == 1
    assert len(open(h).read().splitlines()) == 2  # unchanged


def test_history_gate_usage_errors(tmp_path):
    b = _write(tmp_path, "b.json", _valid_payload())
    # --history replaces the baseline positional
    assert bench_trend.main(["--history", str(tmp_path / "h.jsonl"),
                             b, b]) == 2
    # --append is meaningless without a trajectory to extend
    assert bench_trend.main([b, b, "--append"]) == 2
    # unreadable trajectory
    assert bench_trend.main(["--history", str(tmp_path / "nope.jsonl"),
                             b]) == 2


def test_committed_history_gates_committed_artifact():
    """The repo's own trajectory must stay schema-valid AND pass its own
    gate against the committed artifact — CI runs exactly this."""
    root = os.path.join(os.path.dirname(__file__), "..")
    hist = os.path.join(root, "results", "BENCH_history.jsonl")
    art = os.path.join(root, "results", "BENCH_sodda.json")
    with open(hist) as f:
        entries = validate_bench.validate_history(f.read())
    assert len(entries) >= 2  # the PR's acceptance criterion
    assert bench_trend.main(["--history", hist, art,
                             "--threshold", "0.5"]) == 0


# ---------------------------------------------------------------------------
# tools/bench_trend.py: the us/iter regression gate between two artifacts.
# ---------------------------------------------------------------------------
def _write(tmp_path, name, payload):
    import json
    p = tmp_path / name
    p.write_text(json.dumps(payload))
    return str(p)


def test_bench_trend_ok_and_regression(tmp_path, capsys):
    base = _valid_payload()
    cur = copy.deepcopy(base)
    # +20% is inside the default 25% gate
    cur["backends"]["reference"]["scan_driver"]["us_per_iter"] = 3.6
    b, c = _write(tmp_path, "b.json", base), _write(tmp_path, "c.json", cur)
    assert bench_trend.main([b, c]) == 0
    # +50% trips it
    cur["backends"]["reference"]["scan_driver"]["us_per_iter"] = 4.5
    c = _write(tmp_path, "c2.json", cur)
    assert bench_trend.main([b, c]) == 1
    assert "REGRESSED" in capsys.readouterr().out
    # ... unless the threshold is raised
    assert bench_trend.main([b, c, "--threshold", "0.6"]) == 0
    # improvements never fail
    cur["backends"]["reference"]["scan_driver"]["us_per_iter"] = 0.5
    assert bench_trend.main([b, _write(tmp_path, "c3.json", cur)]) == 0


def test_bench_trend_new_and_dropped_backends_do_not_fail(tmp_path, capsys):
    base = _valid_payload()
    cur = copy.deepcopy(base)
    cur["backends"]["experimental"] = copy.deepcopy(
        cur["backends"]["reference"])
    del cur["backends"]["reference"]
    code = bench_trend.main([_write(tmp_path, "b.json", base),
                             _write(tmp_path, "c.json", cur)])
    out = capsys.readouterr().out
    assert code == 0
    assert "new" in out and "dropped" in out


def test_bench_trend_incomparable_artifacts(tmp_path, capsys):
    base = _valid_payload()
    cur = copy.deepcopy(base)
    cur["iters"] = 99  # a different measurement regime, not a trend
    assert bench_trend.main([_write(tmp_path, "b.json", base),
                             _write(tmp_path, "c.json", cur)]) == 3
    assert "INCOMPARABLE" in capsys.readouterr().out
    cur = copy.deepcopy(base)
    cur["problem"]["M"] = 64
    assert bench_trend.main([_write(tmp_path, "b.json", base),
                             _write(tmp_path, "c2.json", cur)]) == 3


def test_bench_trend_usage_errors(tmp_path):
    b = _write(tmp_path, "b.json", _valid_payload())
    assert bench_trend.main([b]) == 2  # missing current
    assert bench_trend.main([b, str(tmp_path / "missing.json")]) == 2
    assert bench_trend.main([b, b, "--threshold", "-1"]) == 2
    broken = tmp_path / "broken.json"
    broken.write_text("{not json")
    assert bench_trend.main([b, str(broken)]) == 2


def test_bench_trend_help_exits_zero(capsys):
    """--help is a successful invocation, not a usage error (the satellite
    fix: argparse's SystemExit(0) was previously swallowed into exit 2)."""
    assert bench_trend.main(["--help"]) == 0
    assert "usage" in capsys.readouterr().out.lower()


def test_bench_trend_empty_backends_is_incomparable(tmp_path, capsys):
    """An artifact with an empty (or missing) backends map carries zero
    measurements — a trend against it must refuse (exit 3), not
    vacuously pass (the satellite fix)."""
    base = _valid_payload()
    empty = copy.deepcopy(base)
    empty["backends"] = {}
    b = _write(tmp_path, "b.json", base)
    e = _write(tmp_path, "e.json", empty)
    assert bench_trend.main([b, e]) == 3
    assert "INCOMPARABLE" in capsys.readouterr().out
    assert bench_trend.main([e, b]) == 3  # either side
    missing = copy.deepcopy(base)
    del missing["backends"]
    assert bench_trend.main(
        [b, _write(tmp_path, "m.json", missing)]) == 3


def test_bench_trend_identical_artifacts_pass(tmp_path):
    b = _write(tmp_path, "b.json", _valid_payload())
    assert bench_trend.main([b, b]) == 0


@pytest.mark.slow
def test_bench_driver_output_validates(tmp_path):
    """End-to-end: the driver bench's real output must satisfy its own
    schema, and the reference backend must clearly beat the python loop
    (the dispatch-overhead claim). Marked slow: it times real wall-clock
    over every backend. The floor is 2x: PR 2 calibrated 3x, but hosts
    where the persistent compilation cache's deserialized executables
    dispatch slower (see the donation note on _cached_segment_run)
    measure a 2.3-3.3x band run to run — and the committed artifact's
    default-regime (iters=240) reference ratio is ~1.7x, so 3x was
    always a regime-specific number, not the invariant. A measurement
    below the floor is re-taken once; a genuine regression (the scan
    path degrading to loop-like dispatch) fails both attempts by a wide
    margin."""
    out = tmp_path / "BENCH_sodda.json"
    # iters=60: the floor was calibrated in this regime (PR 2). The bench
    # default is higher to amortize fixed dispatch cost across all cells,
    # which changes the loop-vs-scan ratio this floor was tuned against.
    for attempt in (1, 2):
        payload = bench_run.bench_driver(iters=60, reps=2, out_path=str(out))
        validate_bench.validate(payload)
        assert out.exists()
        ref = payload["backends"]["reference"]
        if ref["speedup"] >= 2.0:
            break
    assert ref["speedup"] >= 2.0, (
        f"scan driver only {ref['speedup']:.2f}x over the python loop "
        f"on both measurement attempts")
