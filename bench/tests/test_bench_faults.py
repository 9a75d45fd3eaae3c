"""The harness, run on the CPU at a small size, and broken underneath.

Everything of a run but the look for a chip: set-up, the open-loop window
over TCP, and the comparison that decides ``correct``. A sound run comes
out correct; each fault a cell can have, planted in the timed path, makes
it come out not correct.
"""
import pytest

from bench import faults, run
from bench.tests.small_cells import CELLS


def _run(cell, seed=2**31 + 99):
    result, _ = run.run_cell(cell, seed, 1.5, trace=False,
                             require_device=False, overrides=CELLS[cell])
    return result


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct(cell):
    result = _run(cell)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 10
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device", "checks"}
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("cell,fault", [
    ("silo_d4096.stream", faults.state_unchanged),
    ("silo_d4096.stream", faults.answer_altered),
    ("silo_d4096.stream", faults.half_left_out),
    ("silo_d4096.stream", faults.delta_not_journaled),
    ("fleet_d2048.burst", faults.answer_altered),
    ("fleet_d2048.burst", faults.half_left_out),
], ids=lambda x: f"_{x.__name__}" if callable(x) else x)
def test_fault_makes_the_run_incorrect(cell, fault, monkeypatch):
    fault(monkeypatch.setattr)
    result = _run(cell)
    assert not result["correct"], result["checks"]

