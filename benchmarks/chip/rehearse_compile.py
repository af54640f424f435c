"""Compile a cell's train step for a described TPU v5e chip, without the chip.

  JAX_PLATFORMS=cpu PYTHONPATH=src python3 benchmarks/chip/rehearse_compile.py \\
      --workload kaggle-mfu [--workload ...]

Prints the step's ``memory_analysis`` (arguments, outputs, temporaries) at the
cell's published widths and batch.  Nothing runs: a compile that passes is
not a chip run, and no time comes from it.
"""
import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import harness  # noqa: E402


def compile_step(cfg, traffic, sharding):
    import jax
    import jax.numpy as jnp
    from repro.core import (CPRManager, Emulator, FailureInjector,
                            SystemParams)
    from repro.models import dlrm as D
    cell_cfg = harness.program_config(cfg)
    sp = SystemParams(T_total=traffic["span_h"], **traffic["system"])
    mgr = CPRManager(traffic["mode"], sp, cfg["table_sizes"],
                     target_pls=traffic["target_pls"], r=traffic["r"],
                     ssu_period=traffic["ssu_period"],
                     seg_size=traffic["seg_size"])
    emu = Emulator(cell_cfg, None, mgr,
                   FailureInjector(0, 0.25, sp.N_emb, sp.T_total),
                   lr=cfg["optimizer"]["lr"])
    step, opt = emu._build_step()
    B = cfg["batch_size"]
    params = jax.eval_shape(lambda k: D.init_dlrm(cell_cfg, k),
                            jax.random.PRNGKey(0))
    ostate = jax.eval_shape(opt.init, params)
    tracker = jax.eval_shape(lambda: mgr.tracker_init(None))
    batch = {"dense": jax.ShapeDtypeStruct((B, cfg["num_dense"]),
                                           jnp.float32),
             "sparse": jax.ShapeDtypeStruct(
                 (B, len(cfg["table_sizes"]), cell_cfg.multi_hot), jnp.int32),
             "label": jax.ShapeDtypeStruct((B,), jnp.float32)}
    args = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                       sharding=sharding),
                        (params, ostate, tracker, batch))
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        return step.lower(*args).compile()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", action="append", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    bench = harness.load_benchmark()
    for name in args.workload:
        wl = harness.find(bench["workloads"], name, "workload")
        cfg = harness.load_config(bench, wl["config"])
        traffic = harness.load_traffic(wl["traffic"])
        mem = compile_step(cfg, traffic, one).memory_analysis()
        print(f"{name}: arguments {mem.argument_size_in_bytes} B, outputs "
              f"{mem.output_size_in_bytes} B, temporaries "
              f"{mem.temp_size_in_bytes} B, aliased "
              f"{mem.alias_size_in_bytes} B", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
