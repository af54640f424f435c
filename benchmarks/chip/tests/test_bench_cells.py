"""Every cell end to end on the CPU at a tiny size: warm-up, sizing, the
window through ``Emulator.run``, the trace reduction and the check."""
import pytest

import tiny_cell


@pytest.mark.parametrize("traffic_over", [None, tiny_cell.SSU],
                         ids=["kaggle-mfu", "kaggle-mfu-ssu"])
def test_cell_runs_correct_on_cpu(traffic_over):
    code, res = tiny_cell.run_tiny("kaggle-mfu", seed=2**31 + 9, steps=None,
                                   trace=1, seconds=0.3,
                                   traffic_over=traffic_over)
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


def test_end_to_end_metrics_on_cpu():
    code, res = tiny_cell.run_tiny("kaggle-mfu", seed=11)
    m = res["metrics"]
    assert set(m) == {"samples_per_s", "save_stall_ms", "restore_ms",
                      "setup_s"}
    assert all(v["value"] > 0 for v in m.values())


def test_full_recovery_traffic_on_cpu():
    """The paper's full-recovery baseline (the deferred ``kaggle-full``
    cell's traffic): whole-table saves and the exact byte check."""
    bench = tiny_cell.harness.load_benchmark()
    wl = tiny_cell.harness.find(bench["workloads"], "kaggle-mfu", "workload")
    cfg, traffic = tiny_cell.tiny(
        tiny_cell.harness.load_config(bench, wl["config"]),
        tiny_cell.harness.load_traffic(wl["traffic"]))
    traffic = dict(traffic, mode="full", tracker_backend="host", span_h=14.0,
                   failures_h=[7.0, 12.6],
                   warmup={"span_h": 2.75, "steps": 16, "failures_h": [2.2]})
    args = tiny_cell.run_cell.parse(["--workload", "kaggle-mfu", "--seed",
                                     "21", "--seconds", "1", "--trace", "0"])
    code, res = tiny_cell.run_cell.run(args, require_tpu=False, cfg=cfg,
                                       traffic=traffic, steps=51)
    assert code == 0 and res["correct"] is True
    assert res["checks"]["full_save_bytes_gap"]["value"] == 0
    assert "selection_mismatch" not in res["checks"]
