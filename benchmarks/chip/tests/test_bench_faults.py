"""A run with the timed path broken underneath reads ``correct`` false:
for each fault a cell can have, planted through the harness's probes."""
import pytest

import tiny_cell


@pytest.mark.parametrize("traffic_over,fault,number", [
    (None, "control", "row_grad_median_gap"),
    (None, "stale_state", "update_norm_gap"),
    (None, "half_batch", "grad_norm_gap"),
    (tiny_cell.SSU, "half_batch", "update_norm_gap"),
    (None, "alter_selection", "selection_mismatch"),
    (None, "alter_restore", "restore_mismatch"),
    (tiny_cell.SSU, "alter_selection", "reservoir_mismatch"),
    (tiny_cell.SSU, "stale_state", "grad_norm_gap"),
], ids=["control", "stale_state", "half_batch", "ssu-half_batch",
        "alter_selection", "alter_restore", "ssu-alter_selection",
        "ssu-stale_state"])
def test_fault_reads_not_correct(traffic_over, fault, number):
    code, res = tiny_cell.run_tiny("kaggle-mfu", faults=(fault,),
                                   traffic_over=traffic_over)
    assert code == 0 and res["correct"] is False and res["failed"] >= 1
    c = res["checks"][number]
    assert c["value"] > c["limit"]


def test_sound_run_reads_correct():
    code, res = tiny_cell.run_tiny("kaggle-mfu")
    assert res["correct"] is True and res["failed"] == 0
