"""The trace reduction on traces built by hand and recorded on the CPU."""
import pytest

import tiny_cell  # noqa: F401
import trace_reduce as tr

MS = 1_000_000   # ns


def _trace():
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ("bench.window", 0, 100 * MS),
        ("bench.step", 0, 5 * MS),
        ("bench.save", 40 * MS, 30 * MS),
        ("bench.failure", 80 * MS, 15 * MS)]}]}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ("%fusion.1 = f32[8] fusion(...)", 5 * MS, 20 * MS),
            ("%fusion.2 = f32[8] fusion(...)", 15 * MS, 20 * MS),  # overlaps
            ("%tracker_select.1 = (s32[2]) custom-call(...)", 50 * MS,
             10 * MS),
            ("%fusion.1 = f32[8] fusion(...)", 95 * MS, 10 * MS)]},  # clipped
        {"name": "XLA Modules", "events": [
            ("jit_step(11)", 5 * MS, 30 * MS),
            ("jit_tracker_select(12)", 50 * MS, 10 * MS),
            ("jit_step(11)", 95 * MS, 10 * MS)]}]}
    return [host, dev]


def test_busy_union_idle_and_modules():
    r = tr.reduce_trace(_trace())
    assert r["window_s"] == pytest.approx(0.1)
    # busy: [5, 35) + [50, 60) + [95, 100) = 45 ms
    assert r["busy_s"] == pytest.approx(0.045)
    assert r["n_devices"] == 1
    assert r["modules"]["jit_step"]["count"] == 2
    assert r["modules"]["jit_step"]["s"] == pytest.approx(0.035)
    assert r["modules"]["jit_tracker_select"]["s"] == pytest.approx(0.010)
    ops = dict(r["device_ops"])
    assert ops["%fusion.1"] == pytest.approx(0.025)
    assert ops["%tracker_select.1"] == pytest.approx(0.010)
    gaps = dict(r["idle_gaps"])
    # idle [0, 5) under the step, [35, 50) with its midpoint in the save,
    # [60, 95) with its midpoint between the save and the failure
    assert gaps["bench.step"] == pytest.approx(0.005)
    assert gaps["bench.save"] == pytest.approx(0.015)
    assert gaps["host:other"] == pytest.approx(0.035)
    assert sum(gaps.values()) == pytest.approx(0.1 - 0.045)


def test_gap_attributed_to_host_span():
    t = _trace()
    t[1]["lines"][0]["events"] = [("%a = x", 0, 40 * MS),
                                  ("%b = x", 75 * MS, 25 * MS)]
    gaps = dict(tr.reduce_trace(t)["idle_gaps"])
    assert gaps == {"bench.save": pytest.approx(0.035)}


def test_merge_and_names():
    assert tr.merge([(5, 9), (1, 3), (2, 4), (9, 10)]) == [[1, 4], [5, 10]]
    assert tr.module_base("jit_step(123)") == "jit_step"
    assert tr.op_name("%fusion.3 = f32[2] fusion(x)") == "%fusion.3"
    assert tr.is_device_plane("/device:TPU:3")
    assert not tr.is_device_plane("/device:TPU:0 SparseCore")


def test_missing_window_is_an_error():
    t = _trace()
    t[0]["lines"][0]["events"] = t[0]["lines"][0]["events"][1:]
    with pytest.raises(ValueError):
        tr.reduce_trace(t)


def test_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        jax.jit(lambda x: (x @ x).sum())(jnp.ones((64, 64))).block_until_ready()
    jax.profiler.stop_trace()
    r = tr.reduce_trace(tr.load_xplane(tr.find_xplane(str(tmp_path))))
    # no TPU plane on the CPU: a window, and no device time claimed
    assert r["window_s"] > 0 and r["n_devices"] == 0 and r["busy_s"] == 0


def test_train_mfu_reads_the_step_program_time():
    import harness
    import yardstick
    cfg = {"arch": "dlrm", "num_dense": 13, "table_sizes": [10] * 26,
           "emb_dim": 16,
           "bottom_mlp": [512, 256, 64, 16], "top_mlp": [512, 256, 1]}
    ctx = {"trace": tr.reduce_trace(_trace()), "cfg": cfg,
           "peaks": {"bf16_flops_per_s": 197e12}, "yardstick": yardstick,
           "counters": {"batch": 2048, "window_steps": 5}}
    got = harness.metric_reader("train_mfu_pct").read(ctx)
    # 2 steps of 2048 samples in 35 ms of jit_step, 2.916 MFLOP a sample
    flops = yardstick.dlrm_train_flops_per_sample(cfg)
    assert flops == 3 * (2 * (13 * 512 + 512 * 256 + 256 * 64 + 64 * 16) +
                         2 * (367 * 512 + 512 * 256 + 256) + 2 * 27 * 27 * 16)
    assert got == pytest.approx(100 * flops * 2048 * 2 / 0.035 / 197e12)
