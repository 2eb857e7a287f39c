"""`correct` on the one-chip cells, at test size on the CPU: true for the
program as it is, false for the control and for each fault planted in the
timed path underneath the harness."""
import pytest

from bench_helpers import plant, plant_control, run_tiny

CELLS = ["small1-fit20-rec20", "small1-fit20-rec1"]


@pytest.mark.parametrize("workload", CELLS)
def test_program_is_correct(tiny_root, fresh_programs, workload):
    result = run_tiny(tiny_root, workload)
    assert result["correct"], result["check"]
    assert result["failed"] == 0 and result["attempted"] >= 1


# the control, the reference in bfloat16, fails every cell; x_bf16, X
# stored in bfloat16, fails the cell that records the objective after the
# first iteration (no number of rec20's output separates it, PERF.md)
@pytest.mark.parametrize("workload,kind", [(CELLS[0], "control"),
                                           (CELLS[1], "control"),
                                           (CELLS[1], "x_bf16")])
def test_control_is_not_correct(tiny_root, fresh_programs, monkeypatch,
                                workload, kind):
    plant_control(monkeypatch, kind)
    result = run_tiny(tiny_root, workload)
    assert not result["correct"], result["check"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(tiny_root, fresh_programs, monkeypatch,
                              workload, fault):
    plant(fault, monkeypatch)
    result = run_tiny(tiny_root, workload)
    assert not result["correct"], result["check"]
