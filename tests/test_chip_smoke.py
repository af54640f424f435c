"""The chip smoke script's phases, rehearsed on the CPU at a tiny size.

``chip_smoke.py`` refuses to run without a TPU; these tests drive the same
phase functions with a scaled-down DLRM and dataset so that a change which
breaks the smoke run's control flow or its checks fails here first.
"""
import dataclasses
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.dlrm import DLRM_KAGGLE, scaled  # noqa: E402
from repro.data.synthetic import ClickLogDataset  # noqa: E402

BATCH = 256


@pytest.fixture(scope="module")
def small():
    cfg = scaled(DLRM_KAGGLE, max_rows=2000)
    ds = ClickLogDataset(cfg.table_sizes, num_samples=4096, seed=3)
    return cfg, ds


@pytest.mark.parametrize("mode,backend", [
    ("full", "host"), ("cpr-mfu", "host"), ("cpr-mfu", "pallas"),
    ("cpr-ssu", "host")])
def test_phase_a_mode(small, mode, backend):
    cfg, ds = small
    out = chip_smoke.phase_a(cfg, ds, "cpu", batch=BATCH,
                             modes=((mode, backend),))
    (s,) = out.values()
    assert s["saves"] >= 1 and s["failures"] >= 1
    if mode != "full":
        assert s["pls"] > 0.0


def test_phase_b_pipe_fleet_and_resume(small, tmp_path):
    cfg, ds = small
    out = chip_smoke.phase_b(cfg, ds, "cpu", str(tmp_path), batch=BATCH)
    assert out["B pipe"]["saves"] >= 1


def test_phase_c_train_cli_defaults():
    cfg = dataclasses.replace(get_config("gemma2-2b").reduced(),
                              num_layers=2)
    s = chip_smoke.phase_c(cfg)
    assert s["saves"] >= 1 and s["failures"] >= 1


def test_phase_r_references():
    out = chip_smoke.phase_r(3, "cpu", batch=128, steps=2)
    assert out["param_rel_err"] < 1e-3 and out["loss_rel_err"] < 1e-3


def test_main_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "platform 'cpu'" in captured.err
