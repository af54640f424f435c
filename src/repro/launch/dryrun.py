import os
os.environ["XLA_FLAGS"] = (os.environ.get("_REPRO_EXTRA_XLA", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run driver.

For one (arch × input-shape × mesh) combination:
  1. lower + compile the full-depth (scan-over-layers) step on the
     production mesh -> memory_analysis (fits-in-HBM proof) + HLO text,
  2. lower + compile 1-repetition and 2-repetition probes (single-pod only)
     -> cost_analysis + collective-bytes extrapolation for the roofline,
  3. write a JSON artifact under artifacts/dryrun/.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b \
      --shape train_4k [--multipod] [--probes] [--out artifacts/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all   # sequential sweep

MUST be a fresh process: the XLA device-count flag above is read at first
jax init (tests and benchmarks see the single real CPU device instead).
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import INPUT_SHAPES, get_config, list_archs
from repro.configs.base import ATTN, LOCAL_ATTN, MOE
from repro.launch import roofline as R
from repro.launch import steps as ST
from repro.launch.mesh import CHIP_HBM_BYTES, make_production_mesh
from repro.sharding import specs as S


def applicable(arch: str, shape_name: str):
    """(runs?, variant, reason) — DESIGN.md §5 skip policy."""
    cfg = get_config(arch)
    shp = INPUT_SHAPES[shape_name]
    if shp.kind == "decode" and not cfg.supports_decode:
        return False, None, "encoder-only: no decode step"
    if shape_name == "long_500k":
        kinds = set(cfg.layer_kinds)
        unbounded = (ATTN in kinds or MOE in kinds)
        if unbounded and cfg.sliding_window == 0:
            # dense/MoE full attention: run the sliding-window variant
            return True, "sw4096", "full attention at 500k KV: sliding-window variant"
        if ATTN in kinds:  # gemma2 global layers: model-sharded KV cache
            return True, None, "global layers use sharded 500k KV cache"
    return True, None, ""


def variant_config(cfg, variant):
    if variant == "sw4096":
        pattern = tuple(LOCAL_ATTN if k in (ATTN,) else k
                        for k in cfg.block_pattern)
        return dataclasses.replace(cfg, block_pattern=pattern,
                                   sliding_window=4096,
                                   name=cfg.name + "-sw4096")
    return cfg


def probe_cfg(cfg, reps: int):
    """A config executing ``reps`` pattern-repetitions inside ONE scan
    iteration (so cost_analysis counts every layer exactly once)."""
    return dataclasses.replace(
        cfg, num_layers=reps * len(cfg.block_pattern),
        block_pattern=cfg.block_pattern * reps,
        name=f"{cfg.name}-probe{reps}")


def lower_one(cfg, shape_name: str, mesh, opt="adam", probe=False,
              microbatches=1):
    """Returns (lowered, compiled, meta)."""
    shp = INPUT_SHAPES[shape_name]
    batch = ST.batch_struct(cfg, shape_name)
    b_spec = S.lm_input_specs(batch, mesh)
    dp = S.batch_axes(mesh)
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.sharding.ctx import activation_sharding
    ns = lambda spec_tree: S.to_shardings(spec_tree, mesh)

    with mesh, activation_sharding(mesh, probe_full_blocks=probe):
        if shp.kind == "train":
            fn, p_st, o_st, p_sp, o_sp = ST.build_train_step(
                cfg, mesh, optimizer=opt, param_dtype=jnp.float32,
                microbatches=microbatches)
            jf = jax.jit(fn,
                         in_shardings=(ns(p_sp), ns(o_sp), ns(b_spec)),
                         out_shardings=(ns(p_sp), ns(o_sp),
                                        NamedSharding(mesh, P())))
            lowered = jf.lower(p_st, o_st, batch)
        elif shp.kind == "prefill":
            fn, p_st, p_sp = ST.build_prefill_step(
                cfg, mesh, param_dtype=jnp.dtype(cfg.dtype))
            logit_spec = NamedSharding(mesh, S.guard(
                mesh, (shp.global_batch, shp.seq_len, cfg.vocab_size),
                P(dp, None, "model")))
            jf = jax.jit(fn, in_shardings=(ns(p_sp), ns(b_spec)),
                         out_shardings=logit_spec)
            lowered = jf.lower(p_st, batch)
        else:  # decode
            fn, p_st, s_st, p_sp, s_sp = ST.build_serve_step(
                cfg, mesh, shape_name, param_dtype=jnp.dtype(cfg.dtype))
            B = shp.global_batch
            tok = jax.ShapeDtypeStruct((B,), jnp.int32)
            tok_spec = NamedSharding(
                mesh, P(dp) if B % _dp_size(mesh) == 0 else P())
            logit_spec = NamedSharding(mesh, S.guard(
                mesh, (B, cfg.vocab_size),
                P(dp if B % _dp_size(mesh) == 0 else None, "model")))
            jf = jax.jit(fn,
                         in_shardings=(ns(p_sp), ns(s_sp), tok_spec, None),
                         out_shardings=(logit_spec, ns(s_sp)))
            lowered = jf.lower(p_st, s_st, tok, jnp.int32(0))
    compiled = lowered.compile()
    return lowered, compiled


def _dp_size(mesh):
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("data", 1) * sizes.get("pod", 1)


def run_pair(arch: str, shape_name: str, multi_pod: bool, probes: bool,
             out_dir: str):
    t0 = time.monotonic()               # duration timer, not a timestamp
    runs, variant, reason = applicable(arch, shape_name)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "variant": variant, "note": reason, "status": "skipped"}
    if not runs:
        return rec
    cfg = variant_config(get_config(arch), variant)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(mesh.devices.size)
    shp = INPUT_SHAPES[shape_name]

    # ---- full-depth compile: memory proof + collective schedule ----
    # auto-tune gradient-accumulation microbatches until the step fits HBM
    # (train shapes only; global batch must stay divisible)
    microbatches = 1
    while True:
        lowered, compiled = lower_one(cfg, shape_name, mesh,
                                      microbatches=microbatches)
        ma = compiled.memory_analysis()
        total = (getattr(ma, "argument_size_in_bytes", 0) +
                 getattr(ma, "output_size_in_bytes", 0) +
                 getattr(ma, "temp_size_in_bytes", 0))
        if (shp.kind != "train" or total <= CHIP_HBM_BYTES
                or microbatches >= 8
                or shp.global_batch % (microbatches * 2)):
            break
        microbatches *= 2
    rec["microbatches"] = microbatches
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll_full = R.collective_bytes(hlo)
    mem_bytes = (getattr(mem, "argument_size_in_bytes", 0) +
                 getattr(mem, "output_size_in_bytes", 0) +
                 getattr(mem, "temp_size_in_bytes", 0) +
                 getattr(mem, "generated_code_size_in_bytes", 0))
    rec.update({
        "status": "ok",
        "compile_s": round(time.monotonic() - t0, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "total_bytes": mem_bytes,
            "fits_16GiB": bool(mem_bytes <= CHIP_HBM_BYTES),
        },
        "collectives_full_hlo": coll_full,   # scan body counted once
        "cost_analysis_raw": {k: v for k, v in compiled.cost_analysis().items()
                              if k in ("flops", "bytes accessed")},
    })

    # ---- probes for roofline extrapolation (single-pod only) ----
    if probes and not multi_pod:
        P_len = len(cfg.block_pattern)
        n_reps = cfg.num_layers // P_len
        rem = cfg.num_layers - n_reps * P_len
        l1, c1 = lower_one(probe_cfg(cfg, 1), shape_name, mesh, probe=True)
        ca1 = c1.cost_analysis()
        cl1 = R.collective_bytes(c1.as_text())
        if n_reps >= 2 or rem:
            l2, c2 = lower_one(probe_cfg(cfg, 2), shape_name, mesh, probe=True)
            ca2 = c2.cost_analysis()
            cl2 = R.collective_bytes(c2.as_text())
        else:
            ca2, cl2 = ca1, cl1
        terms = R.extrapolate(ca1, ca2, cl1, cl2, n_reps, rem, P_len, chips,
                              R.analytic_model_flops(cfg, shp))
        rec["roofline"] = terms.as_dict()
        rec["probe_cost"] = {"p1": ca1, "p2": ca2, "coll1": cl1, "coll2": cl2}
    rec["wall_s"] = round(time.monotonic() - t0, 1)
    return rec


def artifact_path(out_dir, arch, shape_name, mesh_name):
    os.makedirs(out_dir, exist_ok=True)
    return os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--probes", action="store_true", default=None)
    ap.add_argument("--no-probes", dest="probes", action="store_false")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()

    pairs = ([(a, s) for a in list_archs() for s in INPUT_SHAPES]
             if args.all else [(args.arch, args.shape)])
    failures = 0
    for arch, shape_name in pairs:
        mesh_name = "pod2x16x16" if args.multipod else "pod16x16"
        probes = args.probes if args.probes is not None else not args.multipod
        try:
            rec = run_pair(arch, shape_name, args.multipod, probes, args.out)
        except Exception as e:  # record the failure; the sweep continues
            rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                   "status": "error", "error": repr(e),
                   "traceback": traceback.format_exc()}
            failures += 1
        with open(artifact_path(args.out, arch, shape_name, mesh_name), "w") as f:
            json.dump(rec, f, indent=1)
        print(json.dumps({k: rec.get(k) for k in
                          ("arch", "shape", "mesh", "status", "note",
                           "compile_s")}), flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
