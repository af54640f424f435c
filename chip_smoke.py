"""Smoke run of CPR's main path on one TPU chip, at published DLRM-Kaggle widths.

  python3 chip_smoke.py [--seed N]

Phase R  references at a small size: the Pallas ``tracker_select`` kernel,
         compiled through Mosaic, against the numpy oracle
         ``kernels.ref.tracker_select`` (exact), and a few DLRM training
         steps on the TPU against the same steps on the host CPU device.
Phase A  DLRM-Kaggle (26 tables at the Criteo Kaggle cardinalities, 33.76M
         rows x 16 fp32, MLPerf MLPs) trained through ``Emulator`` +
         ``CPRManager`` at batch 2048 in modes full, cpr-mfu (host top-k and
         Pallas ``tracker_select`` trackers) and cpr-ssu, each with save events
         and one pinned failure restored by (partial) recovery.
Phase B  cpr-mfu with the shard-writer fleet in child processes (pipe
         transport) writing a checkpoint directory, then one training step
         resumed from that directory.
Phase C  the train CLI's documented LM command, in process: gemma2-2b
         ``--reduced --layers 2 --mode cpr-mfu`` with the CLI's default
         tracker backend.

Cut to size: the synthetic click log holds 32,768 samples (the tables keep
their full cardinalities), and each mode trains 10 steps.  The weights are
random and the data is made from ``--seed``.  Step times printed here are
leads, not benchmarks.

Exits non-zero, printing no result, unless JAX's first device is a TPU.  The
last line of standard output is one JSON object naming the device.
"""
import argparse
import dataclasses
import json
import math
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

BATCH = 2048
STEPS = 10                 # train steps per mode (simulated hour per step)
NUM_SAMPLES = 32_768       # cut: 14 train batches + a 10 % eval split
LM_STEPS = 30              # Phase C, as in the CLI's documented command
REF_ROWS = 5000            # Phase R: small tables, compared element-wise

_compile_s = [0.0]         # backend compile seconds, summed by a listener


def log(msg):
    print(msg, flush=True)


def check(cond, why="check failed"):
    """A smoke check that ``python -O`` cannot strip (unlike ``assert``)."""
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {why!r}")


def smoke_params(steps=STEPS):
    """One simulated hour per step.  With T_fail = the run length and
    O_save = 0.8 h, full recovery saves at t = 4, 8; the cpr modes take the
    partial interval T_save = 8 h (target PLS 0.05), i.e. one priority save
    event per step and a cycle boundary at t = 8."""
    from repro.core import SystemParams
    return SystemParams(T_total=float(steps), T_fail=float(steps), N_emb=8,
                        O_save=0.8 * steps / 10)


def pinned_failure(p, steps=STEPS):
    """One failure of shards {1, 5} at 85 % of the run (after the cycle
    boundary, so partial recovery loses the samples since it)."""
    from repro.core import FailureInjector
    return FailureInjector(1, 0.25, p.N_emb, p.T_total,
                           times=(0.85 * steps,), shard_sets=((1, 5),))


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def check_run(label, res, emu, cfg, platform, mode):
    """Assert what every Phase A/B run must show; returns a summary dict."""
    rep = res.report
    saves = sum(1 for h in emu.mgr.history if h["event"] == "save")
    fails = sum(1 for h in emu.mgr.history if h["event"] == "failure")
    check(math.isfinite(res.final_loss), (label, res.final_loss))
    check(0.0 < res.auc < 1.0, (label, res.auc))
    tables = emu.final_params["tables"]
    check(len(tables) == len(cfg.table_sizes), label)
    for t, n in zip(tables, cfg.table_sizes):
        check(t.shape == (n, cfg.emb_dim), (label, t.shape, n))
        check({d.platform for d in t.devices()} == {platform},
              (label, t.devices()))
    check(saves >= 1, (label, "no save event"))
    check(fails >= 1, (label, "no failure"))
    check(rep["n_failures"] >= 1, label)
    check(rep["shard_failures"] == [], (label, rep["shard_failures"]))
    if mode != "full":
        check(rep["effective_mode"] == mode, (label, rep["effective_mode"]))
        check(rep["measured_pls"] > 0.0, (label, rep["measured_pls"]))
    return {"auc": res.auc, "loss": res.final_loss,
            "pls": rep["measured_pls"], "saves": saves, "failures": fails,
            "save_blocked_s": rep["overheads"]["save_blocked_s"]}


def _timed(label, fn):
    c0, t0 = _compile_s[0], time.monotonic()
    out = fn()
    wall = time.monotonic() - t0
    log(f"[{label}] wall_s={wall:.3f} compile_s={_compile_s[0] - c0:.3f} "
        f"peak_bytes_in_use={_peak_bytes()}")
    return out


def phase_r(seed, platform, batch=256, steps=4):
    """Small-size references.  The Mosaic ``tracker_select`` must match the
    numpy oracle exactly; DLRM steps on the default device must match the
    same steps on the host CPU device (both at full f32 matmul precision)."""
    import jax
    import numpy as np
    from repro.configs.dlrm import DLRM_KAGGLE, scaled
    from repro.core import CPRManager, Emulator, FailureInjector
    from repro.data.synthetic import ClickLogDataset
    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed)
    n, seg, k = REF_ROWS, 512, 8
    counts = rng.integers(0, 50, n).astype(np.int32)
    pending = rng.integers(-3, n + 3, 3000).astype(np.int32)  # some invalid
    lowered = jax.jit(ops.tracker_select, static_argnames=("k", "seg_size")
                      ).lower(counts, pending, k=k, seg_size=seg)
    if platform == "tpu":
        check("tpu_custom_call" in lowered.as_text(), "kernel not Mosaic")
    ids, new = ops.tracker_select(counts, pending, k, seg_size=seg)
    want_ids, want_new = ref.tracker_select(counts, pending, k, seg_size=seg)
    np.testing.assert_array_equal(np.asarray(ids), want_ids)
    np.testing.assert_array_equal(np.asarray(new), want_new)
    log(f"[R tracker_select] ok: {n} rows, seg {seg}, k {k}, exact")

    cfg = scaled(DLRM_KAGGLE, max_rows=REF_ROWS)
    ds = ClickLogDataset(cfg.table_sizes, num_dense=cfg.num_dense,
                         num_samples=(steps + 2) * batch, seed=seed)

    def train_on(device):
        p = smoke_params(steps)
        mgr = CPRManager("full", p, cfg.table_sizes)
        emu = Emulator(cfg, ds, mgr, FailureInjector(0, 0.25, p.N_emb,
                                                     p.T_total),
                       batch_size=batch, seed=seed)
        with jax.default_device(device), \
                jax.default_matmul_precision("highest"):
            res = emu.run(max_steps=steps)
        return res, jax.tree.leaves(emu.final_params)

    res, got = train_on(jax.devices()[0])
    res_cpu, want = train_on(jax.devices("cpu")[0])
    check({d.platform for d in got[0].devices()} == {platform},
          ("R", got[0].devices()))
    rel = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))) /
                    (float(np.max(np.abs(np.asarray(b)))) + 1e-12))
              for a, b in zip(got, want))
    loss_rel = abs(res.final_loss - res_cpu.final_loss) / res_cpu.final_loss
    log(f"[R dlrm vs cpu] {steps} steps at batch {batch}: "
        f"max param rel err {rel:.3e}, loss rel err {loss_rel:.3e}")
    check(rel < 1e-3 and loss_rel < 1e-3, (rel, loss_rel))
    return {"param_rel_err": rel, "loss_rel_err": loss_rel}


def phase_a(cfg, ds, platform, batch=BATCH, steps=STEPS,
            modes=(("full", "host"), ("cpr-mfu", "host"),
                   ("cpr-mfu", "pallas"), ("cpr-ssu", "host"))):
    """DLRM training with saves and a pinned failure, one run per mode."""
    from repro.core import CPRManager, Emulator
    out = {}
    for mode, backend in modes:
        label = f"A {mode}/{backend}"
        p = smoke_params(steps)
        mgr = CPRManager(mode, p, cfg.table_sizes, target_pls=0.05,
                         tracker_backend=backend)
        emu = Emulator(cfg, ds, mgr, pinned_failure(p, steps),
                       batch_size=batch)
        res = _timed(label, lambda: emu.run(max_steps=steps))
        out[label] = s = check_run(label, res, emu, cfg, platform, mode)
        log(f"[{label}] ok " + json.dumps(s))
    return out


def phase_b(cfg, ds, platform, ckpt_dir, batch=BATCH, steps=STEPS):
    """cpr-mfu with per-shard writer processes, then resume one step."""
    from repro.core import CPRManager, Emulator, FailureInjector
    label = "B pipe"
    p = smoke_params(steps)
    mgr = CPRManager("cpr-mfu", p, cfg.table_sizes, target_pls=0.05,
                     directory=ckpt_dir, sharded_save=True, transport="pipe")
    emu = Emulator(cfg, ds, mgr, pinned_failure(p, steps), batch_size=batch)
    res = _timed(label, lambda: emu.run(max_steps=steps))
    s = check_run(label, res, emu, cfg, platform, "cpr-mfu")
    rep = res.report
    check(rep["writer_backend"] == "pipe", rep["writer_backend"])
    check(rep["poisoned_shards"] == [], rep["poisoned_shards"])
    check(sum(rep["shard_events"]) > 0, rep["shard_events"])
    log(f"[{label}] ok " + json.dumps(s))

    label = "B resume"
    p2 = smoke_params(steps)
    mgr2 = CPRManager("cpr-mfu", p2, cfg.table_sizes, target_pls=0.05)
    emu2 = Emulator(cfg, ds, mgr2, FailureInjector(0, 0.25, p2.N_emb,
                                                   p2.T_total),
                    batch_size=batch)
    res2 = _timed(label, lambda: emu2.run(max_steps=1, resume_from=ckpt_dir))
    check(math.isfinite(res2.final_loss), res2.final_loss)
    check(0.0 < res2.auc < 1.0, res2.auc)
    # the resumed model carries the trained state: its AUC stays near the
    # run that wrote the checkpoint, not at an untrained model's
    check(abs(res2.auc - res.auc) < 0.05, (res2.auc, res.auc))
    log(f"[{label}] ok auc={res2.auc:.4f} loss={res2.final_loss:.4f}")
    return {"B pipe": s, "B resume": {"auc": res2.auc,
                                      "loss": res2.final_loss}}


def phase_c(cfg, steps=LM_STEPS):
    """The train CLI's documented cpr-mfu command, with its defaults."""
    from repro.launch.train import train
    label = "C train-cli"
    _, hist = _timed(label, lambda: train(cfg, steps=steps, mode="cpr-mfu"))
    rep = hist["report"]
    losses = [v for _, v in hist["loss"]]
    check(losses and all(math.isfinite(v) for v in losses), losses)
    check(rep["tracker_backend"] == "pallas", rep["tracker_backend"])
    saves = sum(1 for e in hist["events"] if e[0] == "save")
    fails = sum(1 for e in hist["events"] if e[0] == "failure")
    check(saves >= 1 and fails >= 1, (saves, fails))
    check(rep["shard_failures"] == [], rep["shard_failures"])
    s = {"loss": losses[-1], "pls": rep["measured_pls"], "saves": saves,
         "failures": fails}
    log(f"[{label}] ok " + json.dumps(s))
    return s


def _on_duration(event, duration, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_s[0] += duration


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=3,
                    help="seed of the synthetic click logs and references")
    args = ap.parse_args(argv)
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    jax.monitoring.register_event_duration_secs_listener(_on_duration)

    from repro.configs import get_config
    from repro.configs.dlrm import CRITEO_KAGGLE_TABLE_SIZES, DLRM_KAGGLE
    from repro.data.synthetic import ClickLogDataset
    cfg = DLRM_KAGGLE
    check(cfg.table_sizes == CRITEO_KAGGLE_TABLE_SIZES, cfg.table_sizes)
    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
        f"{cfg.name}: {cfg.total_emb_rows()} rows x {cfg.emb_dim}, "
        f"batch {BATCH}, {NUM_SAMPLES} samples (cut), {STEPS} steps/mode")
    _timed("R", lambda: phase_r(args.seed, "tpu"))
    t0 = time.monotonic()
    ds = ClickLogDataset(cfg.table_sizes, num_dense=cfg.num_dense,
                         num_samples=NUM_SAMPLES, seed=args.seed)
    log(f"[data] wall_s={time.monotonic() - t0:.3f} ctr={ds.ctr:.4f}")

    phase_a(cfg, ds, "tpu")
    with tempfile.TemporaryDirectory(prefix="cpr-smoke-") as ckpt:
        phase_b(cfg, ds, "tpu", ckpt)
    lm = dataclasses.replace(get_config("gemma2-2b").reduced(), num_layers=2)
    phase_c(lm)
    log(f"total backend compile_s={_compile_s[0]:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
