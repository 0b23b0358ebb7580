"""The controls of ``correct``: the reference computed one precision lower
and put in the program's place comes out not correct, while the program
itself stays inside every limit.  Tiny sizes on the CPU; the readings at
the cells' own sizes are in PERF.md (``bench/calibrate.py`` on the chip).
"""
import json

import pytest

from bench import harness as H
from bench.tests._run import all_cells, run

CONTROL = {"train_dp": "control_fp8"}


@pytest.mark.parametrize("cell,traffic", all_cells())
def test_control_fails_and_program_passes(cell, traffic):
    traffic = H.load_json(H.traffic_path(traffic))
    control = CONTROL[traffic["kind"]]
    p = run(["bench/calibrate.py", "--workload", cell, "--rehearse",
             "--seeds", "11", "--control-seeds", "11"])
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    limits = dict(traffic["limits"],
                  **traffic.get("rehearse", {}).get("limits", {}))
    assert all(v <= limits[k] for k, v in out["program"].items()), out
    assert any(v > limits[k] for k, v in out[control].items()), out
