"""Each cell's run at rehearsal sizes on the CPU: it passes its checks
and prints no result line.  Without a TPU a real run exits 2 and prints
none."""
import pytest

from bench import harness as H
from bench.tests._run import all_cells, run


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", [c for c, _ in all_cells()])
def test_rehearsal_passes_without_result_line(cell, trace):
    p = run(["bench/run_cell.py", "--workload", cell, "--rehearse",
             "--seconds", "1", "--trace", trace, "--seed", 2**33 + 5])
    assert p.returncode == 0, p.stderr[-3000:]
    assert "rehearsal passed" in p.stderr
    assert p.stdout.strip() == ""


def test_no_tpu_exits_without_result():
    cell = H.load_manifest()["workloads"][0]["name"]
    p = run(["bench/run_cell.py", "--workload", cell, "--seed", "1",
             "--seconds", "1", "--trace", "0"])
    assert p.returncode == 2, p.stderr[-3000:]
    assert p.stdout.strip() == ""
