"""Cells cut to a size a CPU test can hold (same code paths, tiny shapes)."""
import dataclasses

from harness import cell as cells

SIZES = {
    "susy_fit": dict(n_train=6000, n_test=1000, num_centers=400),
    "millionsongs_fit": dict(n_train=4000, n_test=800, num_centers=300),
}


def small_cell(name: str) -> cells.Cell:
    c = cells.load_cell(name)
    return dataclasses.replace(c, config=dict(c.config, **SIZES[name]))
