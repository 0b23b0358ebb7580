"""The training cell's run with its timed path broken underneath comes
out not correct, once for each fault the cell can have."""
import json

import pytest

from bench.tests._run import run

FAULTS = ["state_unchanged", "half_batch", "exchange_left_out",
          "answer_altered", "sum_not_mean"]


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_caught(fault):
    p = run(["bench/tests/fault_driver.py", "qwen3-dp2-gradsync", fault])
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is False, p.stderr[-3000:]
    # Not correct by the comparison, or by the daemon's own deadlock
    # timeout: never by a fault of the test's set-up.
    assert out["error"] is None or "DeadlockTimeout" in out["error"], out
