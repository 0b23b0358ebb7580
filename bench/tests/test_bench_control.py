"""The controls of ``correct``: each cell's generator puts its controls
(the reference computed one precision lower) in the program's place under
``control_<name>``, and one of them comes out not correct, while the
program itself stays inside every limit.  Tiny sizes on the CPU; the
readings at the cells' own sizes are in PERF.md (``bench/calibrate.py``
on the chip).
"""
import json

import pytest

from bench import harness as H
from bench.tests._run import all_cells, run


@pytest.mark.parametrize("cell,traffic", all_cells())
def test_control_fails_and_program_passes(cell, traffic):
    traffic = H.load_json(H.traffic_path(traffic))
    p = run(["bench/calibrate.py", "--workload", cell, "--rehearse",
             "--seeds", "11", "--control-seeds", "11"])
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    limits = dict(traffic["limits"],
                  **traffic.get("rehearse", {}).get("limits", {}))
    assert out["program"], out
    assert all(v <= limits[k] for k, v in out["program"].items()), out
    controls = [v for k, v in out.items() if k.startswith("control_")]
    assert controls, out
    assert any(v > limits[k] for c in controls for k, v in c.items()), out
