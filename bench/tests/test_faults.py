"""A whole run with the timed path broken underneath reads ``correct``
false, once for each fault the cell can have, and once with the data
sweep alone cut to ``high``; the sound run reads true. The look for a chip
is skipped; everything else is the run's own code."""
import argparse
import json

import pytest

import calibrate
import run
from harness import cell as cells
from small import small_cell

CASES = [(w, f) for w in ("susy_fit", "millionsongs_fit")
         for f in (None, "unchanged", "half", "altered", "sweep_high")]


@pytest.mark.parametrize("workload,fault", CASES)
def test_run_reads_the_fault(workload, fault):
    cells.program_path()
    cell = small_cell(workload)
    args = argparse.Namespace(workload=workload, seed=2**33 + 5, seconds=1.0, trace=0)
    with calibrate.planted(fault or "program", cell.config):
        line, _ = run.run(args, require_tpu=False, cell=cell)
    json.dumps(line)
    assert line["correct"] is (fault is None), line["checks"]
    assert list(line)[-1] == "checks"
    if fault == "sweep_high":      # seen by the sweep's own entries
        entry = line["checks"]["sweep_entry_gap"]
        assert entry["value"] > entry["limit"], line["checks"]
