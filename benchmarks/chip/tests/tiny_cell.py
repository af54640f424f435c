"""Shared helpers of the benchmark's CPU tests: a cell at a tiny size."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402
import run_cell  # noqa: E402


def tiny(cfg, traffic, rows=1500, batch=128):
    """Tables cut to at most ``rows`` rows (relative sizes kept), a small
    batch and pool; widths, mode, span and failures as the cell has them."""
    top = max(cfg["table_sizes"])
    cfg = dict(cfg, batch_size=batch, table_sizes=[
        max(4, min(n, n * rows // top)) if n > 100 else n
        for n in cfg["table_sizes"]])
    traffic = dict(traffic, min_window_steps=48,
                   pool=dict(traffic["pool"], train_batches=8,
                             eval_samples=256))
    return cfg, traffic


# the priority-save traffic with SSU reservoirs in place of MFU counts
SSU = {"mode": "cpr-ssu", "tracker_backend": "host"}
# steps of a tiny full-recovery run: its 56-h span's first save lands
# after step 3, inside the window
FULL_STEPS = 130


def run_tiny(workload, seed=5, faults=(), steps=51, trace=0, seconds=1,
             traffic_over=None, cfg_over=None):
    bench = harness.load_benchmark()
    wl = harness.find(bench["workloads"], workload, "workload")
    cfg, traffic = tiny(harness.load_config(bench, wl["config"]),
                        harness.load_traffic(wl["traffic"]))
    cfg = dict(cfg, **(cfg_over or {}))
    traffic = dict(traffic, **(traffic_over or {}))
    args = run_cell.parse(["--workload", workload, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace)])
    return run_cell.run(args, require_tpu=False, cfg=cfg, traffic=traffic,
                        faults=faults, steps=steps)
