"""The stage readers (``bench/scopes.py``) at test size on the CPU: the cell's
program compiled again, a trace planted with known seconds per instruction,
and each stage's milliseconds per iteration read back. Also the four-chip
cell through ``bench/``'s own files, which must stay the mesh fixtures."""
import filecmp
import json
import os

import pytest

from bench_helpers import plant, plant_control, run_tiny

from bench import scopes, spec

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
MESH = os.path.join(os.path.dirname(__file__), "fixtures", "mesh")
FOUR_CHIP = "small2x2-fit20-rec5"
ONE_CHIP = "small1-fit20-rec20"
READERS = ("issue_ms", "exchange_ms", "consume_ms", "objective_ms",
           "unscoped_ms")


def _program(root, workload):
    cell = spec.load_cell(workload, root)
    stages = scopes._program_stages(
        json.dumps(cell.config, sort_keys=True), cell.fit_iters,
        cell.record_every)
    return cell, stages


def _plant(stages, per_name_s):
    """op_s with `per_name_s[stage]` seconds on each of up to three
    instructions of each stage, and the seconds planted per stage."""
    op_s, planted = {}, {}
    for stage, s in per_name_s.items():
        names = sorted(n for n, st in stages.items() if st == stage)[:3]
        op_s.update(dict.fromkeys(names, s))
        planted[stage] = s * len(names)
    return op_s, planted


def _ctx(cell, op_s, fits=4, chips=None):
    return {"config": cell.config, "traffic": cell.traffic,
            "chips": chips or cell.chips, "fits": fits,
            "trace": {"op_s": op_s}}


def _read(root, cell, ctx):
    return {name: spec.metric_reader(name, root)(ctx) for name in READERS}


@pytest.mark.parametrize("workload,chips", [(ONE_CHIP, 1), (FOUR_CHIP, 4)])
def test_each_stage_reads_what_was_planted(tiny_root, workload, chips):
    cell, stages = _program(tiny_root, workload)
    seconds = {"issue": 0.030, "consume": 0.070, "objective": 0.002,
               "unscoped": 0.001}
    if chips == 4:
        seconds["exchange"] = 0.005
    op_s, planted = _plant(stages, seconds)
    fits = 4
    iters = fits * cell.fit_iters
    got = _read(tiny_root, cell, _ctx(cell, op_s, fits, chips))
    for stage, s in planted.items():
        assert s > 0
        assert got[f"{stage}_ms"] == pytest.approx(1e3 * s / chips / iters)
    # the five readers sum to the busy time per iteration and chip
    busy_ms = 1e3 * sum(op_s.values()) / chips / iters
    assert sum(v for v in got.values() if v) == pytest.approx(busy_ms)
    if chips == 1:
        assert got["exchange_ms"] is None  # no collective on one chip


def test_a_name_the_program_lacks_counts_against_coverage(tiny_root):
    cell, stages = _program(tiny_root, ONE_CHIP)
    op_s, _ = _plant(stages, {"issue": 0.3, "consume": 0.6,
                              "unscoped": 0.1})
    total = sum(op_s.values())
    ctx = _ctx(cell, dict(op_s, **{"fusion.init-program": 0.005 * total}))
    got = _read(tiny_root, cell, ctx)
    # inside the 1 % the harness's own programs may take: read, without it
    assert got["consume_ms"] == pytest.approx(
        1e3 * 1.8 / (4 * cell.fit_iters))
    ctx = _ctx(cell, dict(op_s, **{"fusion.init-program": 0.02 * total}))
    assert _read(tiny_root, cell, ctx) == dict.fromkeys(READERS)


def test_a_program_without_scopes_reads_nothing():
    stages = {"fusion.1": "unscoped", "copy.2": "unscoped"}
    assert scopes.split({"fusion.1": 1.0}, stages, 1, 20) is None


def test_a_scope_that_labels_nothing_reads_nothing():
    stages = {"fusion.1": "issue", "fusion.2": "consume", "copy.3":
              "unscoped", "fusion.4": "objective"}
    got = scopes.split({"fusion.1": 0.2, "fusion.2": 0.6, "fusion.4": 0.0},
                       stages, 1, 10)
    assert got == {"issue": pytest.approx(20.0),
                   "consume": pytest.approx(60.0), "objective": 0.0,
                   "unscoped": 0.0, "exchange": None}


def test_a_program_that_cannot_be_compiled_reads_nothing(tiny_root):
    cell = spec.load_cell(ONE_CHIP, tiny_root)
    ctx = _ctx(cell, {"fusion.1": 1.0})
    ctx["config"] = dict(cell.config, backend="no-such-backend")
    assert _read(tiny_root, cell, ctx) == dict.fromkeys(READERS)


def test_the_innermost_scope_is_the_stage():
    assert scopes.scope_of("jit(_run)/while/body/shard_map/sodda.consume/"
                           "sodda.exchange/all_gather") == "exchange"
    assert scopes.scope_of("jit(_run)/sodda.objective/reduce_sum") == \
        "objective"
    assert scopes.scope_of("jit(_run)/while/body/copy") == "unscoped"
    assert scopes.scope_of("jit(_run)/sodda.issue2/add") == "unscoped"


HLO = """HloModule m

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %slice_fusion.1 = f32[8] fusion(%p), kind=kLoop, calls=%fused
  ROOT %tuple.2 = (s32[], f32[8]) tuple(%p, %slice_fusion.1)
}

%cond (p: (s32[], f32[8])) -> pred[] {
  ROOT %lt.3 = pred[] compare(%p, %p), direction=LT, \
metadata={op_name="jit(f)/while/cond/lt"}
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8] parameter(0)
  %copy.4 = f32[8] copy(%x)
  %while.5 = (s32[], f32[8]) while(%copy.4), condition=%cond, body=%body, \
metadata={op_name="jit(f)/sodda.consume/vmap(vmap())/gather"}
  ROOT %fusion.6 = f32[8] fusion(%while.5), kind=kLoop, calls=%fused.2, \
metadata={op_name="jit(f)/sodda.issue/sodda.exchange/psum"}
}
"""


def test_an_instruction_without_metadata_takes_its_callers_stage():
    got = scopes.instruction_stages(HLO)
    # the gather's expansion: the while's body has no metadata of its own
    assert got["slice_fusion.1"] == got["tuple.2"] == "consume"
    assert got["while.5"] == "consume" and got["fusion.6"] == "exchange"
    # metadata without a scope, or none in the entry: outside every scope
    assert got["lt.3"] == got["copy.4"] == got["x"] == "unscoped"


@pytest.mark.parametrize("path,fixture", [
    ("bench/configs/table1-small-2x2.json", "table1-small-2x2.json"),
    ("bench/traffic/fit20-rec5.json", "fit20-rec5.json")])
def test_the_four_chip_cell_is_the_mesh_fixture(path, fixture):
    assert filecmp.cmp(os.path.join(REPO, path), os.path.join(MESH, fixture),
                       shallow=False)


def test_the_four_chip_cell_holds_the_fixtures_numbers():
    """The limits were read on four chips (PERF.md); the fixture's are the
    CPU-proven ones, with the same numbers held over as many fits."""
    with open(os.path.join(REPO, "bench", "limits", f"{FOUR_CHIP}.json")) \
            as f:
        chip = json.load(f)
    with open(os.path.join(MESH, f"{FOUR_CHIP}.json")) as f:
        fixture = json.load(f)
    assert chip["check_fits"] == fixture["check_fits"]
    assert set(chip["limits"]) == set(fixture["limits"])


def test_the_four_chip_cell_is_correct(tiny_root, fresh_programs):
    result = run_tiny(tiny_root, FOUR_CHIP)
    assert result["correct"], result["check"]
    assert result["device"]["count"] == 4
    assert result["failed"] == 0 and result["attempted"] >= 1


# under the limits read on the chip, the control and every fault planted
# in the timed path still fail the four-chip cell
@pytest.mark.parametrize("fault", ["control", "unchanged", "half_batch",
                                   "altered", "no_exchange"])
def test_the_four_chip_cells_limits_fail_every_fault(tiny_root,
                                                     fresh_programs,
                                                     monkeypatch, fault):
    if fault == "control":
        plant_control(monkeypatch)
    else:
        plant(fault, monkeypatch)
    result = run_tiny(tiny_root, FOUR_CHIP)
    assert not result["correct"], result["check"]
