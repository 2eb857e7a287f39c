"""The benchmark harness on the CPU: its files, counts, trace reduction,
generator and refusal to run off the chip."""
import gzip
import json
import os
import re
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, REPO)

from bench import check, data, spec, trace, work  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE = re.compile(r"^[^\n\t]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark(REPO)


def test_every_entry_resolves_to_its_files(bench):
    paths = bench["paths"]
    for c in bench["configs"]:
        assert any(c["file"].startswith(p + "/") for p in paths), c["file"]
        with open(os.path.join(REPO, c["file"])) as f:
            assert json.load(f)["name"] == c["name"]
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], REPO)
        assert cell.chips == w["chips"] and cell.fit_iters >= 1
        held = set(cell.limits["limits"])
        assert {"obj_gap", "w_gap"} <= held <= set(check.NAMES)
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"], REPO))
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word.split("/")


def test_names_units_and_keys_keep_to_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    names = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"] + [c["name"]])
        assert LINE.match(c["why"]) and LINE.match(c["source"])
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert m["name"] not in names
        names.add(m["name"])
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"]: w for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and LINE.match(m["layer"])
        assert set(m.get("workloads", cells)) <= set(cells)
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and LINE.match(w["why"])
        assert w["chips"] in (1, 4)
        per_layer = [m for m in bench["per_layer"]
                     if w["name"] in m.get("workloads", [w["name"]])]
        assert per_layer and len(bench["end_to_end"]) >= 2
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(cells) // 2)


def _copy_benchmark(dest):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dest)
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return str(dest)


def test_a_cell_and_a_metric_are_added_as_files_alone(tmp_path):
    root = _copy_benchmark(tmp_path)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append(
        {"name": "small1-fit10-rec2", "config": "table1-small-n10k",
         "traffic": "fit10-rec2", "chips": 1, "why": "a test's cell"})
    bench["per_layer"].append(
        {"name": "fits_seen", "unit": "fits", "better": "higher",
         "source": "host_clock", "layer": "device", "moves": "iters_per_s",
         "workloads": ["small1-fit10-rec2"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    with open(os.path.join(root, "bench", "traffic", "fit10-rec2.json"),
              "w") as f:
        json.dump({"fit_iters": 10, "record_every": 2}, f)
    shutil.copy(
        os.path.join(root, "bench", "limits", "small1-fit20-rec20.json"),
        os.path.join(root, "bench", "limits", "small1-fit10-rec2.json"))
    with open(os.path.join(root, "bench", "metrics", "fits_seen.py"),
              "w") as f:
        f.write("def read(ctx):\n    return float(ctx['fits'])\n")

    cell = spec.load_cell("small1-fit10-rec2", root)
    assert (cell.fit_iters, cell.record_every, cell.chips) == (10, 2, 1)
    assert cell.config["name"] == "table1-small-n10k"
    assert [m["name"] for m in cell.per_layer][-1] == "fits_seen"
    ctx = {"fits": 3, "compile_s": 1.5, "data_s": 0.5}
    got = {m["name"]: spec.metric_reader(m["name"], root)(ctx)
           for m in cell.per_layer if m["name"] in ("fits_seen", "data_s")}
    assert got == {"fits_seen": 3.0, "data_s": 0.5}
    assert "fits_seen" not in [
        m["name"] for m in spec.load_cell("small1-fit20-rec20",
                                          root).per_layer]


ONE_CHIP = "bench/configs/table1-small-n10k.json"
FOUR_CHIP = "tests/bench/fixtures/mesh/table1-small-2x2.json"


@pytest.mark.parametrize("config,iters,every,flops,nbytes", [
    # 50,000 x 18,000, 5x3: |B| 15,300, |C| 14,400, |D| 8,500 of n 10,000;
    # passes 19 * 0.7225 + 1 + 1; inner 15 chains of 64 x 1,200
    (ONE_CHIP, 20, 20, 54_228_240_000, 56_715_556_800),
    # passes 0 * 0.7225 + 20 + 1 when every iteration records
    (ONE_CHIP, 20, 1, 50_628_240_000 + 21 * 1.8e9,
     21 * 3.6e9 + 96_556_800),
    # 250,000 x 18,000, 2x2: |D| 106,250 of n 125,000; passes
    # 16 * 0.7225 + 4 + 1; inner 4 chains of 64 x 4,500
    (FOUR_CHIP, 20, 5, 297_588_240_000, 298_176_500_480),
])
def test_work_is_counted_by_hand(config, iters, every, flops, nbytes):
    with open(os.path.join(REPO, config)) as f:
        cfg = json.load(f)
    got = work.fit_work(cfg, iters, every)
    assert got["flops"] == pytest.approx(flops, rel=1e-12)
    assert got["bytes"] == pytest.approx(nbytes, rel=1e-12)


def test_peaks_are_keyed_by_device_kind():
    v5e = work.peaks("TPU v5 lite")
    assert (v5e["flops_per_s"], v5e["hbm_bytes_per_s"]) == (197e12, 819e9)
    with pytest.raises(KeyError):
        work.peaks("cpu")
    assert work.least_seconds({"flops": 197e12, "bytes": 819e9 * 3}, v5e,
                              1) == pytest.approx(3.0)


def test_reduce_clips_nests_merges_and_names_gaps():
    ms = 1_000_000
    tr = {"host": [("fit.dispatch", 0, 10 * ms), ("fit.fetch", 10 * ms,
                                                   20 * ms)],
          "devices": {
              "/device:TPU:0": [("fusion.1", -5 * ms, 2 * ms),
                                ("while.2", 2 * ms, 6 * ms),
                                ("sodda_inner.3", 2 * ms, 3 * ms),
                                ("fusion.4", 4 * ms, 6 * ms),
                                ("all-reduce.5", 15 * ms, 16 * ms),
                                ("fusion.1", 18 * ms, 30 * ms)],
              "/device:TPU:1": [("fusion.1", 0, 20 * ms)]},
          "async": {"/device:TPU:0": [("all-gather-start.6", 14 * ms,
                                       15.5 * ms)]}}
    got = trace.reduce(tr)
    assert got["chips"] == 2
    assert got["window_s"] == pytest.approx(0.020)
    # chip 0 busy 0-6, 15-16, 18-20 = 9 ms; chip 1 all 20 ms
    assert got["busy_s"] == pytest.approx((0.009 + 0.020) / 2)
    assert got["kernel_s"] == pytest.approx(0.001)
    assert got["kernel_calls"] == 1
    # all-gather 14-15.5 and all-reduce 15-16 on chip 0: 2 ms of union
    assert got["collective_s"] == pytest.approx(0.002)
    # the while loop's self time leaves out the ops nested in it
    assert got["op_s"]["while.2"] == pytest.approx(0.001)
    assert got["op_s"]["fusion.1"] == pytest.approx(0.002 + 0.002 + 0.020)
    assert sum(got["op_s"].values()) == pytest.approx(0.009 + 0.020)
    assert got["idle_gaps"][0] == ["fit.fetch", pytest.approx(0.009)]
    assert got["idle_gaps"][1] == ["fit.fetch", pytest.approx(0.002)]


def test_op_names_are_cut_to_the_instruction():
    assert trace.op_name("%fusion.12 = f32[8]{0} fusion(f32[8] %p)") == \
        "fusion.12"
    assert trace.op_name("sodda_inner") == "sodda_inner"


def test_reduce_a_trace_recorded_on_the_chip():
    """Two fits of a traced run on one v5e: 20 kernel calls each, no
    collectives, busy time equal to the ops' self times summed."""
    with gzip.open(os.path.join(FIXTURE, "one_chip_trace.json.gz"),
                   "rt") as f:
        tr = json.load(f)
    got = trace.reduce(tr)
    assert got["chips"] == 1
    assert got["window_s"] == pytest.approx(0.02401793, rel=1e-9)
    assert got["busy_s"] == pytest.approx(0.017180367, rel=1e-9)
    assert 1 - got["busy_s"] / got["window_s"] == pytest.approx(0.28468,
                                                                abs=1e-5)
    assert got["kernel_calls"] == 40
    assert got["kernel_s"] == pytest.approx(0.007457242, rel=1e-9)
    assert got["collective_s"] == 0.0
    assert sum(got["op_s"].values()) == pytest.approx(got["busy_s"])
    assert got["device_ops"][0][0] == "sodda_inner.11"
    assert {n for n, _ in got["idle_gaps"]} <= set(trace.HOST_SPANS)


def _stats(X, y):
    X, y = np.asarray(X, np.float64), np.asarray(y)
    # the planted separator, recovered by least squares, labels ~99% right
    w = np.linalg.lstsq(X, y, rcond=None)[0]
    return X.mean(), X.var(), X.min(), X.max(), (y > 0).mean(), \
        (np.sign(X @ w) == y).mean()


@pytest.mark.parametrize("grid", [(1, 1), (2, 2)])
def test_generator_matches_the_papers_data_in_distribution(grid):
    from jax.sharding import AxisType, Mesh

    from repro.data import synthetic

    N, M = 20_000, 16
    mesh = None if grid == (1, 1) else Mesh(
        np.array(jax.devices()[:4]).reshape(grid), ("data", "model"),
        axis_types=(AxisType.Auto,) * 2)
    _, key = data.seed_keys(2**32 + 17)
    X, y = data.generate(key, N, M, mesh=mesh, device=jax.devices()[0])
    assert X.shape == (N, M) and y.shape == (N,)
    ours = _stats(X, y)

    base = jax.random.PRNGKey(3)
    P, Q = 2, 2
    Xs = jnp.concatenate([jnp.concatenate(
        [synthetic.svm_tile_x(base, p, q, N // P, M // Q) for q in range(Q)],
        axis=1) for p in range(P)])
    ys = jnp.concatenate([synthetic.svm_label_block(base, p, N // P, Q,
                                                    M // Q)
                          for p in range(P)])
    theirs = _stats(Xs, ys)
    for a, b, tol in zip(ours, theirs, (0.01, 0.01, 1e-3, 1e-3, 0.02, 0.01)):
        assert a == pytest.approx(b, abs=tol)
    assert ours[2] >= -np.sqrt(3) and ours[3] <= np.sqrt(3)
    assert 0.97 < ours[5] < 0.995


def test_seeds_past_32_bits_give_other_data_and_one_seed_the_same():
    def first(seed):
        run_key, key = data.seed_keys(seed)
        X, _ = data.generate(key, 64, 8, device=jax.devices()[0])
        return np.asarray(run_key), np.asarray(X)

    a, b, c = first(5), first(5 + 2**32), first(5)
    assert not np.array_equal(a[0], b[0]) and not np.array_equal(a[1], b[1])
    assert np.array_equal(a[0], c[0]) and np.array_equal(a[1], c[1])


def _command(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "small1-fit20-rec20",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_fails_off_the_tpu_and_prints_no_result():
    proc = _command(REPO)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_command_fails_with_only_the_benchmarks_files(tmp_path):
    bench = spec.load_benchmark(REPO)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _command(str(tmp_path))
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "No module named 'repro'" in proc.stderr


def test_first_objective_gap_reads_the_tick_after_the_start():
    ref_w = np.ones(4)
    r = check.readings([1.0, 0.5, 0.25], ref_w * 1.001, [1.0, 0.5005, 0.5],
                       ref_w)
    assert r["obj_gap_first"] == pytest.approx(0.0005 / 0.5005)
    assert r["obj_gap"] == pytest.approx(0.5)
    assert r["w_gap"] == pytest.approx(0.001)


@pytest.mark.parametrize("hist,w", [([1.0, np.nan], np.ones(4)),
                                    ([1.0, 0.5], np.full(4, np.inf)),
                                    ([1.0], np.ones(4)),
                                    ([1.0, 0.5, 0.4], np.ones(4)),
                                    ([1.0, 0.5], np.ones(3))])
def test_an_answer_not_finite_or_misshapen_fails_every_number(hist, w):
    r = check.readings(hist, w, [1.0, 0.5], np.ones(4))
    assert all(np.isinf(r[k]) for k in check.NAMES)
    assert not check.fit_ok(r, {"obj_gap": 1e9})


def test_judge_holds_only_the_numbers_the_limits_name():
    numbers = {"obj_gap": 1e-5, "w_gap": 1e-3, "obj_gap_first": 1.0}
    ok, table = check.judge(numbers, {"obj_gap": 2e-3, "w_gap": 0.05})
    assert ok and set(table) == {"obj_gap", "w_gap"}
    ok, table = check.judge(numbers, {"obj_gap": 2e-3, "w_gap": 0.05,
                                      "obj_gap_first": 1e-6})
    assert not ok and table["obj_gap_first"] == {"value": 1.0,
                                                 "limit": 1e-6}
