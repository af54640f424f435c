"""The ``dcnv2-mfu`` cell (MLPerf's DLRM-DCNv2 as one chip's share): its
configuration, the reference's counts, and the cell end to end on the CPU at
a tiny size, sound and with each training fault planted."""
import dataclasses

import pytest

import tiny_cell
import harness
import yardstick

CELL = "dcnv2-mfu"


@pytest.fixture(scope="module")
def cfg():
    return harness.load_config(harness.load_benchmark(), "dlrm-dcnv2")


def test_train_flops_per_sample_by_hand(cfg):
    # bottom 13*512 + 512*256 + 256*128 = 170,496 MACs; three cross layers
    # of 3456*512 + 512*3456 = 10,616,832; top 3456*1024 + 1024*1024 +
    # 1024*512 + 512*256 + 256*1 = 5,243,136.  2 ops a MAC, x3 for training.
    ref = harness.reference(cfg["arch"])
    assert ref.forward_flops_per_sample(cfg) == 2 * 16_030_464 == 32_060_928
    assert ref.train_flops_per_sample(cfg) == 96_182_784
    assert yardstick.dlrm_train_flops_per_sample(cfg) == 96_182_784


def test_configuration_is_the_published_widths_and_a_64_chip_share(cfg):
    dcfg = harness.program_config(cfg)
    assert (dcfg.interaction, dcfg.dcn_num_layers, dcfg.dcn_low_rank_dim,
            dcfg.emb_dim, dcfg.interaction_dim) == ("dcn", 3, 512, 128, 3456)
    assert dcfg.bottom_mlp == (512, 256, 128)
    assert dcfg.top_mlp == (1024, 1024, 512, 256, 1)
    published = cfg["reduced"]["table_sizes"]["published"]
    assert sum(published) == 204_184_588
    assert list(cfg["table_sizes"]) == [-(-n // 64) if n >= 65_536 else n
                                        for n in published]
    assert sum(cfg["table_sizes"]) == 3_309_690
    assert sum(cfg["reduced"]["multi_hot"]["published"]) == 214
    assert cfg["multi_hot"] == 8
    bench = harness.load_benchmark()
    entry = harness.find(bench["configs"], "dlrm-dcnv2", "configuration")
    assert set(entry["reduced"]) == set(cfg["reduced"])
    assert len(entry["source"]) <= 200


def test_full_save_bytes_by_hand():
    ref = harness.reference("dlrm_dcnv2")
    arch = ref.Arch(2, (3, 5), 4, (8, 4), (6, 1), 2, 3, 1)
    # tables 8 rows x 4 + 8 accumulators; bottom 2*8+8 + 8*4+4; cross over
    # 3 * 4 = 12 wide, 2 layers of 12*3 + 3*12 + 12; top 12*6+6 + 6*1+1
    dense = (16 + 8 + 32 + 4) + 2 * (36 + 36 + 12) + (72 + 6 + 6 + 1)
    assert ref.full_save_bytes(arch) == 4 * (8 * 4 + 8 + dense)


def test_a_program_without_the_cross_fields_stops_at_set_up(cfg, monkeypatch):
    """A program whose DLRMConfig has no cross layers (this benchmark's
    parent) refuses the configuration at set-up, naming the keys."""
    from repro.models import dlrm as D

    @dataclasses.dataclass(frozen=True)
    class DotOnly:
        name: str
        num_dense: int
        table_sizes: tuple
        emb_dim: int
        bottom_mlp: tuple
        top_mlp: tuple
        multi_hot: int = 1
        source: str = ""

    monkeypatch.setattr(D, "DLRMConfig", DotOnly)
    with pytest.raises(ValueError, match="dcn_low_rank_dim, dcn_num_layers, "
                                         "interaction"):
        harness.program_config(cfg)


def test_cell_runs_correct_on_cpu():
    code, res = tiny_cell.run_tiny(CELL, seed=2**31 + 17)
    assert code == 0 and res["correct"] is True and res["failed"] == 0
    checks = res["checks"]
    assert checks["events_gap"]["value"] == 0
    assert checks["selection_mismatch"]["value"] == 0
    assert checks["restore_mismatch"]["value"] == 0
    assert all(c["value"] <= c["limit"] for c in checks.values())
    assert set(res["metrics"]) == {"samples_per_s", "save_stall_ms",
                                   "setup_s"}


@pytest.mark.parametrize("fault,number", [
    ("control", "row_grad_median_gap"),
    ("stale_state", "update_norm_gap"),
    ("half_batch", "grad_norm_gap"),
])
def test_fault_reads_not_correct(fault, number):
    code, res = tiny_cell.run_tiny(CELL, faults=(fault,))
    assert code == 0 and res["correct"] is False and res["failed"] >= 1
    c = res["checks"][number]
    assert c["value"] > c["limit"]
    assert res["checks"]["events_gap"]["value"] == 0
