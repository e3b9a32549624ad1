"""The control: the reference in fp8, put in the program's place in every
window of the sample and judged by the same comparison, comes out as not
correct where the served path passes.  At the smoke size on the CPU, with
the smoke limits; on the chip at the cell's size the readings that set
the cell's limits come from ``bench/control.py``."""

import time

import pytest

from bench import harness as H
from bench import spec as S


@pytest.mark.parametrize("name", S.list_cells())
def test_fp8_control_fails_where_the_program_passes(name):
    cell = S.resolve_cell(name)
    res = H.run_cell(cell, 3, 4.0, False, t_start=time.perf_counter(),
                     rehearse=True, control=True, log=lambda s: None)
    prog = res["program_checks"]
    assert all(c["value"] <= c["limit"] for c in prog.values()), prog
    assert res["checks_pass"] is False
    limit = cell.config["smoke"]["limits"]["logit_gap"]
    assert res["checks"]["logit_gap"]["value"] > 3 * limit
