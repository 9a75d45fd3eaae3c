"""The four-chip cell at test size, on a 2x2 mesh of four CPU host devices.

The device count is fixed before JAX starts, so one child process runs
every scenario of this file (a sound run, the control, and each fault the
cell can have, planted in turn) and prints one JSON line each; the tests
read those lines. The harness's look for a chip is skipped; everything
else of a run is as on the chip: wire admission into a sharded tenant,
warm-up, the open-loop window over TCP, and the comparison that decides
``correct``.
"""
import json
import os
import pathlib
import subprocess
import sys

import pytest

from bench import spec

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "silo_d16384.read"

CHILD = """
import json
from bench import deploy, faults, run
from bench.tests.small_cells import FOUR_CHIP_CELLS

placed = []
real_check = deploy.check_placement
def spy(dep):
    placed.append(real_check(dep))
    return placed[-1]
deploy.check_placement = spy

def scenario(name, fault=None, control=False):
    undo = []
    def patch(obj, attr, value):
        undo.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, value)
    if fault is not None:
        faults.FAULTS[fault](patch)
    try:
        result, _ = run.run_cell({cell!r}, 2**31 + 77, 1.5, trace=False,
                                 control=control, require_device=False,
                                 overrides=FOUR_CHIP_CELLS[{cell!r}])
    finally:
        for obj, attr, value in reversed(undo):
            setattr(obj, attr, value)
    summary = placed[-1] if placed else {{}}
    print("SCENARIO " + json.dumps({{
        "name": name, "correct": result["correct"],
        "attempted": result["attempted"], "failed": result["failed"],
        "checks": result["checks"], "device": result["device"],
        "placements": summary.get("placements"),
        "meshes_built": summary.get("meshes_built")}}), flush=True)
    placed.clear()

scenario("sound")
scenario("control", control=True)
for fault in {faults!r}:
    scenario(fault, fault=fault)
"""

#: The faults this cell can have: its state is made by admission and read
#: by lone sharded solves (no delta, no stacked sweep, no journaled delta).
FAULTS = ["state_unchanged", "answer_altered", "exchange_left_out"]


@pytest.fixture(scope="module")
def scenarios():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    proc = subprocess.run(
        [sys.executable, "-c", CHILD.format(cell=CELL, faults=FAULTS)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    out = {}
    for line in proc.stdout.splitlines():
        if line.startswith("SCENARIO "):
            row = json.loads(line[len("SCENARIO "):])
            out[row["name"]] = row
    return out


def test_sound_run_is_correct_on_the_mesh(scenarios):
    row = scenarios["sound"]
    assert row["correct"], row["checks"]
    assert row["failed"] == 0 and row["attempted"] > 10
    assert row["device"]["count"] == 4
    assert row["placements"] == {"sharded": 1}
    assert row["meshes_built"] == 1


def test_control_reads_far_above_the_program_on_the_mesh(scenarios):
    row = scenarios["control"]
    limit = spec.config(spec.workload(CELL)["config"])["limits"]["w_rel_err"]
    program = row["checks"]["w_rel_err"]["value"]
    assert program < limit / 10
    assert row["checks"]["control_w_rel_err"]["value"] > 5 * program
    assert row["correct"]


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_makes_the_sharded_run_incorrect(scenarios, fault):
    row = scenarios[fault]
    assert not row["correct"], row["checks"]
