"""Benchmark harness: one module per paper table/figure.

Usage:
  PYTHONPATH=src python -m benchmarks.run [--only fig7,fig13] [--fast]

Prints ``name,us_per_call,derived`` CSV lines (one per benchmark row; the
``derived`` field is the row's JSON payload) and writes
``artifacts/bench/<name>.json``.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import time

from repro.launch.compile_cache import enable_compile_cache

BENCHMARKS = [
    ("fig3", "benchmarks.fig3_failure_model", {}),
    ("fig6", "benchmarks.fig6_freq_update_corr", {}),
    ("fig7", "benchmarks.fig7_overhead", {}),
    ("fig8", "benchmarks.fig8_production", {}),
    ("fig9", "benchmarks.fig9_pls_sensitivity", {}),
    ("fig10", "benchmarks.fig10_failures", {}),
    ("fig11", "benchmarks.fig11_pls_accuracy", {}),
    ("fig12", "benchmarks.fig12_ssu_slope", {}),
    ("fig13", "benchmarks.fig13_scalability", {}),
    ("fig14", "benchmarks.fig14_async_save", {}),
    ("fig15", "benchmarks.fig15_sharded_save", {}),
    ("fig16", "benchmarks.fig16_reshard", {}),
    ("fig17", "benchmarks.fig17_wire", {}),
    ("table1", "benchmarks.table1_trackers", {}),
]

FAST_OVERRIDES = {
    "fig7": {"datasets": ("kaggle",)},
    "fig11": {"n_points": 6},
    "fig10": {"n_failures": (2, 20)},
    "fig14": {"max_rows": (20_000,), "events": 3,
              "select_sizes": (50_000,)},
    # lost_shards keeps the bytes_lost_at_crash parity-vs-stamped audit
    # (kill a writer, reconstruct from peers) in the benchmark smoke job
    "fig15": {"max_rows": 8_000, "n_shards": (1, 2, 4), "events": 3,
              "lost_shards": (2, 4)},
    "fig16": {"max_rows": 6_000, "n_ops": 3},
    "fig17": {"max_rows": 6_000, "events": 3, "hash_rows": 20_000},
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None

    os.makedirs("artifacts/bench", exist_ok=True)
    print("name,us_per_call,derived")
    for name, module, kwargs in BENCHMARKS:
        if only and name not in only:
            continue
        kw = dict(kwargs)
        if args.fast and name in FAST_OVERRIDES:
            kw.update(FAST_OVERRIDES[name])
        mod = importlib.import_module(module)
        t0 = time.perf_counter()
        rows = mod.run(**kw)
        us = (time.perf_counter() - t0) * 1e6 / max(len(rows), 1)
        with open(f"artifacts/bench/{name}.json", "w") as f:
            json.dump(rows, f, indent=1)
        for row in rows:
            print(f"{name},{us:.0f},{json.dumps(row)}", flush=True)


if __name__ == "__main__":
    main()
