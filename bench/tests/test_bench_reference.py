"""The plain reference against the program, scheme by scheme, on one link
and on three, at a size the CPU holds."""
import os

import pytest

from bench import check, grid

SCHEMES = ("dcqcn", "pseudo_ack", "themis", "matchrdma", "geopipe",
           "sdr_rdma", "rdmacell")


@pytest.mark.parametrize("name", ["fig3cd_congestion", "multipath3_skew"])
@pytest.mark.parametrize("scheme", SCHEMES)
def test_reference_matches_program(tiny_root, name, scheme):
    from repro.netsim import sweep_grid
    cell = grid.Cell(tiny_root, name)
    cells = cell.cells(2 ** 31 + 5)
    rows = sweep_grid(grid.to_program(cells), (scheme,),
                      horizon_us=cell.horizon_us, trace_mode="metrics")
    picks = {scheme: list(range(len(cells)))}
    refs = check.reference_rows(cell, cells, picks)
    gaps = check.gaps(rows, refs, (scheme,))
    assert len(gaps) == len(cells)
    # float32 roundoff only: the same arithmetic on the same backend
    assert max(g for g, _, _ in gaps) < 1e-5


def test_reference_rows_carry_the_program_columns(tiny_root):
    from repro.netsim import sweep_grid
    cell = grid.Cell(tiny_root, "fig3b_msgsize")
    cells = cell.cells(11)
    rows = sweep_grid(grid.to_program(cells), cell.schemes,
                      horizon_us=cell.horizon_us, trace_mode="metrics")
    refs = check.reference_rows(cell, cells, check.sample(
        len(cells), cell.schemes, 11))
    for si, s in enumerate(cell.schemes):
        for i, ref in refs[s].items():
            assert set(ref) == set(rows[i * len(cell.schemes) + si])
    assert os.path.basename(cell.config["reference"]) == "fluid.py"
