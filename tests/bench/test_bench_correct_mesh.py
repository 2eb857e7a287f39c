"""`correct` on the four-chip cell, at test size on four of the CPU's
forced host devices: true for the program as it is, false for the control
and for each fault planted in the timed path, the exchange left out among
them.

The cell is not in ``BENCHMARK.json``: it has no runs on four chips yet.
Its configuration, traffic and limits are fixtures here, and this file adds
them and its entries to a test checkout.
"""
import os

import pytest

from bench_helpers import make_tiny_root, plant, plant_control, run_tiny

MESH = os.path.join(os.path.dirname(__file__), "fixtures", "mesh")
CELL = "small2x2-fit20-rec5"
CONFIG = {"name": "table1-small-2x2", "source": "Fang & Klabjan 2018",
          "file": "bench/configs/table1-small-2x2.json",
          "reduced": ["P", "Q", "lr0"], "why": "Table 1 SMALL on a 2x2 mesh"}
WORKLOAD = {"name": CELL, "config": "table1-small-2x2",
            "traffic": "fit20-rec5", "chips": 4, "why": "the 2x2 mesh"}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    files = [(os.path.join(MESH, "table1-small-2x2.json"), CONFIG["file"]),
             (os.path.join(MESH, "fit20-rec5.json"),
              "bench/traffic/fit20-rec5.json"),
             (os.path.join(MESH, f"{CELL}.json"), f"bench/limits/{CELL}.json")]
    return make_tiny_root(str(tmp_path_factory.mktemp("tiny_mesh")),
                          configs=[CONFIG], workloads=[WORKLOAD], files=files)


def test_program_is_correct(tiny_root, fresh_programs):
    result = run_tiny(tiny_root, CELL)
    assert result["correct"], result["check"]
    assert result["device"]["count"] == 4


def test_control_is_not_correct(tiny_root, fresh_programs, monkeypatch):
    plant_control(monkeypatch)
    assert not run_tiny(tiny_root, CELL)["correct"]


@pytest.mark.parametrize("fault",
                         ["unchanged", "half_batch", "altered", "no_exchange"])
def test_fault_is_not_correct(tiny_root, fresh_programs, monkeypatch, fault):
    plant(fault, monkeypatch)
    result = run_tiny(tiny_root, CELL)
    assert not result["correct"], result["check"]
