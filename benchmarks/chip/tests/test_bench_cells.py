"""Every cell end to end on the CPU at a tiny size: warm-up, sizing, the
window through ``Emulator.run``, the trace reduction and the check."""
import pytest

import tiny_cell


@pytest.mark.parametrize("traffic_over,cfg_over", [
    (None, None), (tiny_cell.SSU, None), (None, {"multi_hot": 2})],
    ids=["kaggle-mfu", "kaggle-mfu-ssu", "kaggle-mfu-multi_hot2"])
def test_cell_runs_correct_on_cpu(traffic_over, cfg_over):
    code, res = tiny_cell.run_tiny("kaggle-mfu", seed=2**31 + 9, steps=None,
                                   trace=1, seconds=0.3,
                                   traffic_over=traffic_over,
                                   cfg_over=cfg_over)
    assert code == 0 and res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 48
    checks = res["checks"]
    assert list(res)[-1] == "checks"
    assert checks["events_gap"]["value"] == 0
    assert all(c["value"] <= c["limit"] for c in checks.values())
    assert res["device"]["platform"] == "cpu"
    # no TPU plane on the CPU: the device-trace metrics stay silent
    assert "device_idle_pct" not in res["metrics"]
    assert res["metrics"]["save_mb_per_event"]["value"] > 0
    if traffic_over is None:
        assert checks["selection_mismatch"]["value"] == 0


def test_end_to_end_metrics_on_cpu():
    code, res = tiny_cell.run_tiny("kaggle-mfu", seed=11)
    m = res["metrics"]
    assert set(m) == {"samples_per_s", "save_stall_ms", "restore_ms",
                      "setup_s"}
    assert all(v["value"] > 0 for v in m.values())


def test_full_recovery_traffic_on_cpu():
    """The paper's full-recovery baseline (the ``kaggle-full`` cell's own
    traffic file): whole-model saves, the exact byte check, and the state
    handed back whole by each restore."""
    code, res = tiny_cell.run_tiny("kaggle-full", seed=21,
                                   steps=tiny_cell.FULL_STEPS)
    assert code == 0 and res["correct"] is True
    checks = res["checks"]
    assert checks["full_save_bytes_gap"]["value"] == 0
    assert checks["events_gap"]["value"] == 0
    assert checks["restore_mismatch"]["value"] == 0
    assert checks["full_image_mismatch"]["value"] == 0
    assert "selection_mismatch" not in checks
    # the program reads no checkpoint at a full-recovery failure, so the
    # cell reports no restore time
    assert set(res["metrics"]) == {"samples_per_s", "save_stall_ms",
                                   "setup_s"}
