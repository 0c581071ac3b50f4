"""The harness's check on the CPU at a small size: sound runs come out
correct, and every fault a one-chip cell can have, planted under the timed
path, comes out not correct (the look for a chip is skipped).

- lose_ack (the control): one acknowledged change per delivery never
  reaches the farm, breaking the stated guarantee;
- stale: a delivery that returns its state unchanged;
- half_batch: half of a delivery's documents left out;
- alter: one served value altered where it is produced.
"""
import time

import pytest

from benchmark import harness

SMALL = {
    "text-512.typing-steady": {"config.docs": 8, "cell.rate_per_s": 12,
                               "config.preload": {"templates": 4,
                                                  "chars": 64, "rows": 65},
                               "traffic.warmup_s": 0.5,
                               "traffic.shape_warmup": {"kind": "ins",
                                                        "sizes": [1, 8]},
                               "cell.batching": {"policy": "docs", "docs": 4,
                                                 "max_wait_s": 0.25}},
}
LISTED = ["text-512.typing-steady"]
# a preload of each document's own (the trace kind), on the same cell
TRACE = ("text-512.typing-steady", dict(
    SMALL["text-512.typing-steady"], **{
        "config.docs": 3, "cell.rate_per_s": 8, "config.capacity": 8192,
        "config.preload": {"kind": "trace", "keystrokes": 6000,
                           "deletions": 1789, "final_chars": 2422,
                           "keystrokes_per_change": 1000,
                           "typing_run_mean": 8, "backspace_run_mean": 4,
                           "jump_p": 0.1, "rows": 6001},
        "cell.batching": {"policy": "docs", "docs": 2, "max_wait_s": 0.25}}))
CASES = {cell: (cell, SMALL[cell]) for cell in LISTED}
CASES["trace-preload"] = TRACE


def run(cell, fault=None, seed=2**33 + 3):
    name, overrides = CASES[cell]
    return harness.run_cell(name, seed, 2.0, False, time.perf_counter(),
                            require_chip=False, fault=fault,
                            overrides=overrides)


@pytest.mark.parametrize("cell", list(CASES))
def test_sound_run_is_correct(cell):
    result = run(cell)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("fault", harness.FAULTS)
@pytest.mark.parametrize("cell", list(CASES))
def test_fault_is_caught(cell, fault):
    result = run(cell, fault)
    assert not result["correct"], (fault, result["checks"])
