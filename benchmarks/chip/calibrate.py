"""Readings that set the check's limits: sound runs, the control, a fault.

  python3 benchmarks/chip/calibrate.py --workload kaggle-mfu \\
      --seeds 1000,1001,1002 --control-seeds 3 --fault-seeds 1 \\
      [--witness 2] [--out FILE]

For each seed, the program's first three steps run through the window run's
own path (``Cell.first_steps``: the same ``Emulator.run``, probes and feed,
stopped before the window) and are compared with the plain reference, as a
benchmark run compares them.  The control is the reference itself put in the
program's place and computed one precision below the configuration's
(``bf16_3x``, the three bfloat16 passes of ``high``, for float32 at
``highest``), as ``run_cell`` computes it under the ``control`` fault.  The
fault is the program with half of each batch left out, the mean taken over
the rest.  A state left unchanged reads 1 on ``update_norm_gap`` by
construction and needs no run.

``--compact-control`` reads the control alone, and the TPU's own ``high``
beside it, against the reference, all three over ``compact``'s model: the
cell's batches and the rows they touch, whose step-0 row gradients are the
full model's.

``--witness N`` adds, on the first N seeds, a third side: the reference in
float64 on the host CPU, over the table rows the three steps touch (no other
row moves or adds to a norm), from the same float32 initial weights.  It prints both float32
sides' gaps to it, and the smallest ReLU input of each step over the
layer's root mean square: a seed on which the program and the float32
reference part by far more than rounding, with a ReLU input within rounding
of zero, parts at a kink.

Needs a TPU unless ``--cpu`` is given.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import checks  # noqa: E402
import harness  # noqa: E402

NUMBERS = ("row_grad_median_gap", "grad_norm_gap", "update_norm_gap",
           "loss_gap", "row_grad_worst_gap")


def compact(cell, batches):
    """The seed's float32 initial weights over the table rows the batches
    touch (each table padded to a fixed number of rows that nothing reads,
    so every seed has one shape), the batches' ids renumbered to them, and
    step 0's rows.  No other row moves or adds to a norm in three steps."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    ref = cell.ref
    p32 = ref._init_jit(cell.arch, jax.random.PRNGKey(cell.seed))
    T = len(cell.arch.table_sizes)
    pad = sum(b["sparse"][:, 0, :].size for b in batches)
    ids = [np.unique(np.concatenate([b["sparse"][:, t, :].ravel()
                                     for b in batches])) for t in range(T)]
    tabs = [np.array(x) for x in ref._take_rows(
        p32["tables"], [jnp.asarray(np.pad(i, (0, pad - i.size)), jnp.int32)
                        for i in ids])]
    for t in range(T):
        tabs[t][ids[t].size:] = 0.0
    params = {"tables": tabs, **jax.device_get({"bottom": p32["bottom"],
                                                "top": p32["top"]})}
    del p32
    local = []
    for b in batches:
        sp = np.empty_like(b["sparse"])
        for t in range(T):
            sp[:, t, :] = np.searchsorted(ids[t], b["sparse"][:, t, :])
        local.append(dict(b, sparse=sp))
    first = [np.searchsorted(ids[t], cell.first_rows[t]) for t in range(T)]
    return params, local, first


def compact_train(cell, model, opt, precision, f64=False):
    """Three reference steps over ``compact``'s model -> the check's
    readings, and each step's smallest |ReLU input| over its layer's root
    mean square.  ``f64``: in float64 on the host CPU."""
    import contextlib
    import jax
    import jax.numpy as jnp
    import numpy as np
    ref = cell.ref
    params, batches, first_local = model
    dt = np.float64 if f64 else np.float32
    three = precision == "bf16_3x"
    ctx = contextlib.ExitStack()
    if f64:
        ctx.enter_context(jax.enable_x64(True))
        ctx.enter_context(jax.default_device(jax.devices("cpu")[0]))
    ctx.enter_context(jax.default_matmul_precision(
        "highest" if three else precision))
    with ctx:
        p = jax.tree.map(lambda x: jnp.asarray(np.asarray(x, dt)), params)
        p0 = jax.tree.map(lambda x: jnp.array(x), p)
        acc = jax.tree.map(lambda x: jnp.zeros(
            x.shape[:1] if x.ndim >= 2 else x.shape, dt), p)
        losses, first, rows, kinks = [], None, None, []
        for b in batches:
            bb = {"dense": jnp.asarray(b["dense"], dt),
                  "label": jnp.asarray(b["label"], dt),
                  "sparse": jnp.asarray(b["sparse"])}
            if f64:
                pre = []
                ref.forward(p, bb["dense"], bb["sparse"], preacts=pre)
                kinks.append(min(float(jnp.min(jnp.abs(a)) /
                                       jnp.sqrt(jnp.mean(jnp.square(a))))
                                 for a in pre))
            p, acc, loss, norms = ref.adagrad_step(
                p, acc, bb, lr=opt["lr"], eps=opt["eps"], three_pass=three)
            losses.append(float(loss))
            if first is None:
                first = ref.leaf_values(norms)
                rows = [np.asarray(x) for x in ref.row_grad_norms(
                    acc, p, [jnp.asarray(r, jnp.int32) for r in first_local])]
        change = ref.leaf_values(jax.tree.map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))), p, p0))
    return ({"losses": losses, "grad_norms": first, "change_norms": change,
             "row_norms": rows}, {"relu_min_over_rms": kinks})


def readings(got, want):
    nums, leaves = checks.training_gaps(got, want)
    return {**nums, **{k: leaves[k] for k in ("loss_gap", "rows_compared",
                                              "row_grad_worst_gap")},
            "leaves": {k: leaves[k] for k in ("grad_norm_gap",
                                              "update_norm_gap")}}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds of the program's runs")
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="the control runs on the first this many seeds")
    ap.add_argument("--fault-seeds", type=int, default=1)
    ap.add_argument("--witness", type=int, default=0,
                    help="the witness runs on the first this many seeds")
    ap.add_argument("--compact-control", action="store_true",
                    help="only the control (and the TPU's own high) against "
                         "the reference, both over the compact model")
    ap.add_argument("--out", default=None)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    wl = harness.find(bench["workloads"], args.workload, "workload")
    import jax
    if not args.cpu and jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    if not args.cpu:
        harness.enable_compile_cache()
    cfg = harness.load_config(bench, wl["config"])
    traffic = harness.load_traffic(wl["traffic"])
    opt = cfg["optimizer"]
    control = harness.CONTROL_PRECISION[cfg["matmul_precision"]]
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        cell = harness.Cell(cfg, traffic, seed)
        batches = [cell.pool_batch(j) for j in range(harness.WINDOW_STEP)]
        if args.compact_control:
            model = compact(cell, batches)
            want, _ = compact_train(cell, model, opt, cfg["matmul_precision"])
            for prec in (control, "high"):
                got, _ = compact_train(cell, model, opt, prec)
                rows.append({"kind": "control" if prec == control
                             else "native_" + prec, "seed": seed,
                             **readings(got, want)})
                print(json.dumps(rows[-1]), flush=True)
            continue

        def ref(precision):
            return cell.ref.train(cell.arch, seed, batches,
                                  precision=precision, lr=opt["lr"],
                                  eps=opt["eps"], first_rows=cell.first_rows)
        want = ref(cfg["matmul_precision"])
        runs = [("program", cell.first_steps())]
        if i < args.control_seeds:
            runs.append(("control", ref(control)))
        if i < args.fault_seeds:
            bad = harness.Cell(cfg, traffic, seed, faults=("half_batch",))
            runs.append(("half_batch", bad.first_steps()))
        for kind, got in runs:
            rows.append({"kind": kind, "seed": seed,
                         **readings(got, want)})
            print(json.dumps(rows[-1]), flush=True)
        if i < args.witness:
            w, kinks = compact_train(cell, compact(cell, batches), opt,
                                     "highest", f64=True)
            rows.append({"kind": "witness", "seed": seed, **kinks,
                         "program_vs_f64": readings(runs[0][1], w),
                         "reference_vs_f64": readings(want, w)})
            print(json.dumps(rows[-1]), flush=True)
    summary = {}
    for kind in ("program", "control", "native_high", "half_batch"):
        sel = [r for r in rows if r["kind"] == kind]
        if sel:
            summary[kind] = {k: (max if kind == "program" else min)(
                r[k] for r in sel) for k in NUMBERS}
    print(json.dumps({"workload": wl["name"], "summary": summary}),
          flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
