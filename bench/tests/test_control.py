"""The control, the plain reference one precision step below the
configuration's (``high`` for fp32 at ``highest``) put in the program's
place, fails the cell's limits; the program passes them. Off the chip
``high`` is emulated as the TPU computes it (reference.falkon_ref.mm)."""
import json

import pytest

import calibrate
from harness import cell as cells
from small import small_cell


def _limits(name):
    return json.loads((cells.BENCH / "limits" / f"{name}.json").read_text())["limits"]


@pytest.mark.parametrize("workload", ["susy_fit", "millionsongs_fit"])
def test_control_fails_and_program_passes(workload):
    cells.program_path()
    cell = small_cell(workload)
    limits = _limits(workload)
    prog, ctrl = (calibrate.fit_readings(cell, 7, v) for v in ("program", "control"))
    assert all(prog[k] <= limits[k] for k in limits), prog
    assert any(ctrl[k] > limits[k] for k in limits), ctrl
