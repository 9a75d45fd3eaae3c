"""The control: the reference one precision step down, in the program's place.

At a size a test run holds, the program reads inside the cell's limit and
the control (three bfloat16 passes) reads several times the program's
reading on the same sampled answers. The gap widens with the size: at the
cells' own sizes on the chip (``python bench/control.py``) the control
reads above each limit; PERF.md has those readings.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, run, spec
from bench.tests.small_cells import CELLS


def test_dot3_is_three_bf16_passes():
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(128, 32)), jnp.float32)
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    err3 = np.abs(np.asarray(control.dot3(a, b)) - exact).max()
    err1 = np.abs(np.asarray(jnp.matmul(
        a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32)) - exact).max()
    assert 1e-6 < err3 < err1 / 20


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_reads_far_above_the_program(cell):
    result, _ = run.run_cell(cell, 2**31 + 5, 1.5, trace=False,
                             control=True, require_device=False,
                             overrides=CELLS[cell])
    checks = result["checks"]
    limit = spec.config(spec.workload(cell)["config"])["limits"]["w_rel_err"]
    program = checks["w_rel_err"]["value"]
    assert program < limit / 10
    assert checks["control_w_rel_err"]["value"] > 5 * program
    assert result["correct"]
