"""Compile the main path's programs for a TPU v5e chip, without the chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology, and refuses what the chip would
refuse (block tilings, VMEM and HBM overruns) — which interpret mode never
shows.  Nothing here runs a program; results and times need the chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

os.environ.setdefault("TPU_LOG_DIR", "disabled")

HBM_BYTES = 16 * 2**30          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def test_dlrm_kaggle_mfu_train_step_fits_one_chip(one_chip):
    """The Emulator's train step at published Kaggle widths (33.76M rows x
    16 fp32, rowwise adagrad, MFU counters on the big tables), B=2048."""
    from repro.configs.dlrm import DLRM_KAGGLE
    from repro.core import CPRManager, Emulator, FailureInjector, SystemParams
    from repro.models import dlrm as D

    cfg, B = DLRM_KAGGLE, 2048
    p = SystemParams()
    mgr = CPRManager("cpr-mfu", p, cfg.table_sizes)
    assert mgr.is_priority
    emu = Emulator(cfg, None, mgr, FailureInjector(0, 0.25, p.N_emb,
                                                   p.T_total))
    step, opt = emu._build_step()
    params = jax.eval_shape(lambda k: D.init_dlrm(cfg, k),
                            jax.random.PRNGKey(0))
    ostate = jax.eval_shape(opt.init, params)
    tracker = jax.eval_shape(lambda: mgr.tracker_init(None))
    batch = {"dense": jax.ShapeDtypeStruct((B, cfg.num_dense), jnp.float32),
             "sparse": jax.ShapeDtypeStruct((B, cfg.num_sparse,
                                             cfg.multi_hot), jnp.int32),
             "label": jax.ShapeDtypeStruct((B,), jnp.float32)}
    compiled = step.lower(*_on(one_chip, (params, ostate, tracker,
                                          batch))).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes +
             mem.temp_size_in_bytes)
    table_bytes = cfg.total_emb_rows() * cfg.emb_dim * 4
    assert mem.argument_size_in_bytes >= table_bytes
    assert total < HBM_BYTES, mem


def test_tracker_select_compiles_through_mosaic(one_chip):
    """The Pallas MFU selection kernel at the largest Kaggle table, with a
    batch of pending ids, lowers to a TPU custom call (not the
    interpreter)."""
    from repro.core import trackers as trk
    from repro.kernels import tracker_select as ts

    n = 10_131_227
    seg, k = trk.segmented_k(n, int(0.125 * n), 512)
    counts = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    pending = jax.ShapeDtypeStruct((2048,), jnp.int32, sharding=one_chip)
    compiled = ts.tracker_select.lower(counts, pending, k, seg_size=seg,
                                       interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
