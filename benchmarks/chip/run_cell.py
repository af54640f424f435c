"""Run one cell of the chip benchmark once and print its result line.

  python3 benchmarks/chip/run_cell.py --workload kaggle-mfu --seed 7 \\
      --seconds 30 --trace 0

From the root of a checkout.  Exits non-zero, printing no result, unless
JAX's first device is a TPU and there are as many as the cell asks for.
With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` (a profiler trace of the window) its per-layer metrics.  The
last line of standard output is one JSON object; the numbers compared with
the reference, each beside its limit, are the last lines of standard error
and the result's last key, ``checks``.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import yardstick  # noqa: E402


def log(msg):
    print(msg, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", action="append", default=[],
                    choices=("control", "half_batch", "stale_state",
                             "alter_selection", "alter_restore",
                             "alter_image"),
                    help="break the timed path underneath, to see the check "
                         "fail (never in a benchmark run)")
    ap.add_argument("--dump", default=None,
                    help="directory for the reduced trace and the run's "
                         "event log (optional)")
    return ap.parse_args(argv)


def device_info(jax, n):
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    return {"platform": dev.platform, "kind": dev.device_kind, "count": n,
            "memory_peak_bytes": stats.get("peak_bytes_in_use")}


def evaluate(cell, probes, limits, image_gap=None):
    """The numbers compared with the reference -> {name: (value, limit)}."""
    import checks
    ref = cell.ref
    prog = probes.readings()
    c0 = harness.CompileCounter.get().snapshot()
    took = {}
    t0 = time.perf_counter()

    def lap(name):
        nonlocal t0
        t1 = time.perf_counter()
        took[name] = round(t1 - t0, 3)
        t0 = t1
    mode = cell.traffic["mode"]
    restores = mode != "full" and bool(probes.failure_states)
    picks, union = checks.restore_picks(cell, probes, ref) if restores \
        else (None, None)
    lap("restore_picks")
    batches = [cell.pool_batch(j) for j in range(harness.WINDOW_STEP)]
    opt = cell.cfg["optimizer"]
    want = ref.train(cell.arch, cell.seed, batches,
                     precision=cell.cfg["matmul_precision"], lr=opt["lr"],
                     eps=opt["eps"], rows=union, first_rows=cell.first_rows,
                     lap=lap)
    if "control" in cell.faults:
        # the control: the reference one precision lower in the program's place
        prog = ref.train(cell.arch, cell.seed, batches,
                         precision=harness.CONTROL_PRECISION[
                             cell.cfg["matmul_precision"]],
                         lr=opt["lr"], eps=opt["eps"],
                         first_rows=cell.first_rows)
        lap("control")
    nums, leaves = checks.training_gaps(prog, want)
    nums["events_gap"] = checks.events_gap(
        probes, harness.expected_events(cell.traffic))
    if mode == "cpr-mfu":
        nums["selection_mismatch"] = checks.selection_mismatch(cell, probes,
                                                               ref)
        lap("selection")
    if mode == "cpr-ssu":
        nums["reservoir_mismatch"] = checks.reservoir_mismatch(cell, probes)
        lap("reservoir")
    if restores:
        detail = {}
        nums["restore_mismatch"] = checks.restore_mismatch(
            cell, probes, ref, picks, union, want["init_rows"], detail)
        leaves["restore_detail"] = detail
        lap("restore")
    if mode == "full":
        nums["restore_mismatch"] = checks.full_restore_mismatch(probes)
        nums["full_save_bytes_gap"] = checks.full_save_bytes_gap(cell, probes,
                                                                 ref)
        nums["full_image_mismatch"] = checks.full_image_mismatch(probes,
                                                                 image_gap)
    leaves["check_s"] = took
    c1 = harness.CompileCounter.get().snapshot()
    leaves["check_compiles"] = [b - a for a, b in zip(c0, c1)]
    out = {k: (float(v), float(limits[k])) for k, v in nums.items()}
    return out, leaves


def end_to_end(cell, probes, setup_s):
    saves = probes.window_events("saves")
    fails = probes.window_events("failures")
    vals = {"samples_per_s": probes.window_steps * cell.batch /
            probes.window_s(),
            "setup_s": setup_s}
    if saves:
        vals["save_stall_ms"] = 1e3 * sum(r["s"] for r in saves) / len(saves)
    if fails:
        vals["restore_ms"] = 1e3 * sum(r["s"] for r in fails) / len(fails)
    return vals


def counters(cell, probes):
    import checks
    saves = probes.window_events("saves")
    tr, sizes = cell.traffic, cell.cfg["table_sizes"]
    calls = []
    if tr["mode"] == "cpr-mfu" and tr["tracker_backend"] == "pallas":
        for t in checks.big_tables(sizes):
            n = sizes[t]
            seg, k = yardstick.segmented_k(
                n, max(1, int(tr["r"] * n)), tr["seg_size"])
            calls.append({"n": n, "k": k, "seg": seg, "pending": 0})
    return {"window_steps": probes.window_steps, "batch": cell.batch,
            "window_s": probes.window_s(), "window_saves": len(saves),
            "window_save_bytes": probes.bytes1 - probes.bytes0,
            "selection_calls_per_save": calls}


def per_layer(bench, workload, ctx):
    out = {}
    for m in harness.metrics_for(bench, workload, "per_layer"):
        v = harness.metric_reader(m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run(args, root=harness.ROOT, require_tpu=True, cfg=None, traffic=None,
        faults=(), steps=None):
    """Returns (exit code, result dict or None)."""
    bench = harness.load_benchmark(root)
    wl = harness.find(bench["workloads"], args.workload, "workload")
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < wl["chips"]):
        print(f"run_cell: needs {wl['chips']} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform!r} device(s) "
              f"({devs[0].device_kind})", file=sys.stderr)
        return 2, None
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    cfg = cfg or harness.load_config(bench, wl["config"], root)
    traffic = traffic or harness.load_traffic(wl["traffic"])
    limits = harness.load_limits()
    if devs[0].platform == "tpu":
        log(f"compile cache: {harness.enable_compile_cache(root)}")
    cell = harness.Cell(cfg, traffic, args.seed, tuple(faults) +
                        tuple(args.fault))
    if steps is None:
        warm = cell.warm_up()
        steps = cell.size(args.seconds)
        log(f"warm-up: step {1e3 * warm.step_s():.3f} ms, saves "
            f"{[round(r['s'], 4) for r in warm.window_events('saves')]} s, "
            f"failures {[round(r['s'], 4) for r in warm.window_events('failures')]} s"
            f" -> {steps} steps")
        del warm
    trace_dir = (os.path.join(root, ".bench", "trace", wl["name"])
                 if args.trace else None)
    probes, emu, res = cell.run(steps, traffic["span_h"],
                                traffic["failures_h"], record=True,
                                trace_dir=trace_dir)
    setup_s = probes.t_start - T_PROCESS
    device = device_info(jax, wl["chips"])
    log(f"window: {probes.window_steps} steps, {probes.window_s():.3f} s, "
        f"saves {[round(r['s'], 4) for r in probes.window_events('saves')]} s, "
        f"failures {[round(r['s'], 4) for r in probes.window_events('failures')]}"
        f" s, bytes saved {probes.bytes1 - probes.bytes0}")
    log(f"compiles in window: {probes.c1[0] - probes.c0[0]} "
        f"(compile requests {probes.c1[1] - probes.c0[1]}, cache hits "
        f"{probes.c1[2] - probes.c0[2]}); "
        f"device peak bytes {device['memory_peak_bytes']}")
    rep = res.report
    log(f"modeled (not measured): overhead fraction "
        f"{rep['overheads'].get('fraction')}, pls {rep['measured_pls']}, "
        f"auc {res.auc:.5f}")
    log(f"setup_s {setup_s:.3f}; window memory (bytes in use, peak) "
        f"{probes.memory}")
    emu.final_params = emu.final_ostate = None
    probes.latest = None
    t_check = time.perf_counter()
    image_gap = probes.image_arrays_differ(emu.mgr.store)
    emu.mgr.store = emu.mgr.writer = None

    trace = None
    if args.trace:
        import trace_reduce
        trace = trace_reduce.reduce_trace(trace_reduce.load_xplane(
            trace_reduce.find_xplane(trace_dir)))
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    nums, leaves = evaluate(cell, probes, limits, image_gap)
    log(f"check took {time.perf_counter() - t_check:.3f} s; whole run "
        f"{time.perf_counter() - T_PROCESS:.3f} s; details {leaves}")
    correct = all(v <= lim for v, lim in nums.values())
    result = {"correct": correct,
              "attempted": probes.window_steps +
              len(probes.window_events("saves")) +
              len(probes.window_events("failures")),
              "failed": sum(1 for v, lim in nums.values() if not v <= lim)}
    if args.trace:
        try:
            peaks = yardstick.peaks(device["kind"])
        except KeyError:
            peaks = None
        ctx = {"trace": trace, "cfg": cfg, "traffic": traffic,
               "peaks": peaks, "counters": counters(cell, probes),
               "yardstick": yardstick}
        result["metrics"] = per_layer(bench, wl["name"], ctx)
        result["device"] = device
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    else:
        vals = end_to_end(cell, probes, setup_s)
        result["metrics"] = {
            m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
            for m in harness.metrics_for(bench, wl["name"], "end_to_end")
            if m["name"] in vals}
        result["device"] = device
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in nums.items()}
    if args.dump:
        os.makedirs(args.dump, exist_ok=True)
        with open(os.path.join(args.dump, f"{wl['name']}-{args.seed}-"
                               f"{args.trace}.json"), "w") as f:
            json.dump({"trace": trace, "saves": probes.saves,
                       "failures": probes.failures, "leaves": leaves,
                       "result": result}, f, default=str)
    for k, (v, lim) in nums.items():
        print(f"check {k} = {v!r} (limit {lim!r}){'' if v <= lim else ' FAIL'}",
              file=sys.stderr)
    return 0, result


def main(argv=None):
    args = parse(argv)
    code, result = run(args)
    if result is not None:
        sys.stderr.flush()
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
