"""A run with the timed path broken underneath reads ``correct`` false:
for each fault a cell can have, planted through the harness's probes."""
import pytest

import tiny_cell

MFU, FULL = "kaggle-mfu", "kaggle-full"


@pytest.mark.parametrize("workload,traffic_over,fault,number", [
    (MFU, None, "control", "row_grad_median_gap"),
    (MFU, None, "stale_state", "update_norm_gap"),
    (MFU, None, "half_batch", "grad_norm_gap"),
    (MFU, tiny_cell.SSU, "half_batch", "update_norm_gap"),
    (MFU, None, "alter_selection", "selection_mismatch"),
    (MFU, None, "alter_restore", "restore_mismatch"),
    (MFU, tiny_cell.SSU, "alter_selection", "reservoir_mismatch"),
    (MFU, tiny_cell.SSU, "stale_state", "grad_norm_gap"),
    (FULL, None, "control", "row_grad_median_gap"),
    (FULL, None, "stale_state", "update_norm_gap"),
    (FULL, None, "half_batch", "grad_norm_gap"),
    (FULL, None, "alter_restore", "restore_mismatch"),
    (FULL, None, "alter_image", "full_image_mismatch"),
], ids=["control", "stale_state", "half_batch", "ssu-half_batch",
        "alter_selection", "alter_restore", "ssu-alter_selection",
        "ssu-stale_state", "full-control", "full-stale_state",
        "full-half_batch", "full-alter_restore", "full-alter_image"])
def test_fault_reads_not_correct(workload, traffic_over, fault, number):
    steps = tiny_cell.FULL_STEPS if workload == FULL else 51
    code, res = tiny_cell.run_tiny(workload, faults=(fault,),
                                   traffic_over=traffic_over, steps=steps)
    assert code == 0 and res["correct"] is False and res["failed"] >= 1
    c = res["checks"][number]
    assert c["value"] > c["limit"]
    assert res["checks"]["events_gap"]["value"] == 0


def test_sound_run_reads_correct():
    code, res = tiny_cell.run_tiny("kaggle-mfu")
    assert res["correct"] is True and res["failed"] == 0
