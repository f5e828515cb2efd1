"""Each benchmark cell rehearsed end to end on the CPU at its
configuration's rehearsal size (interpreted kernels)."""
import json

import pytest

from bench import run
from bench.cell import ROOT, load_cell

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearsal_is_correct_with_no_compile_in_the_window(name):
    cell = load_cell(name)
    result, numbers = run.run_cell(cell, 2**31 + 11, 3.0, False,
                                   rehearse=True)
    assert result["correct"], numbers
    assert result["compiles_in_window"] == 0
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert result["device"]["platform"] == "cpu"
    assert list(result)[-1] == "check"
