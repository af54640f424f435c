"""Paper-faithful DLRM configs with Criteo table cardinalities.

The emulation framework (paper §5.1) trains the MLPerf reference DLRM.  The
real Criteo datasets are not available offline, so the data pipeline
generates a synthetic click log with the same feature layout and Zipf-like
categorical statistics; ``scaled()`` shrinks table cardinalities so a full
emulated training run fits the CPU budget while keeping the 26-table layout
and the skewed access distribution that CPR-MFU/SSU exploit.
"""
from __future__ import annotations

import dataclasses

from repro.models.dlrm import DLRM_DCNV2 as _DCNV2_BASE
from repro.models.dlrm import DLRM_KAGGLE as _KAGGLE_BASE
from repro.models.dlrm import DLRM_TERABYTE as _TERABYTE_BASE

# Criteo Kaggle (Display Advertising Challenge) categorical cardinalities.
CRITEO_KAGGLE_TABLE_SIZES = (
    1460, 583, 10131227, 2202608, 305, 24, 12517, 633, 3, 93145, 5683,
    8351593, 3194, 27, 14992, 5461306, 10, 5652, 2173, 4, 7046547, 18, 15,
    286181, 105, 142572,
)

# Criteo Terabyte cardinalities (MLPerf reference, day-0..23, capped at 40M).
CRITEO_TERABYTE_TABLE_SIZES = (
    39884406, 39043, 17289, 7420, 20263, 3, 7120, 1543, 63, 38532951,
    2953546, 403346, 10, 2208, 11938, 155, 4, 976, 14, 39979771, 25641295,
    39664984, 585935, 12972, 108, 36,
)

# MLPerf DLRM-DCNv2's Criteo 1TB multi-hot cardinalities (capped at 40M) and
# lookups per table (214 a sample).
CRITEO_1TB_MLPERF_TABLE_SIZES = (
    40000000, 39060, 17295, 7424, 20265, 3, 7122, 1543, 63, 40000000,
    3067956, 405282, 10, 2209, 11938, 155, 4, 976, 14, 40000000, 40000000,
    40000000, 590152, 12973, 108, 36,
)
MLPERF_DCNV2_HOTNESS = (
    3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12, 100, 27,
    10, 3, 1, 1,
)

DLRM_KAGGLE = dataclasses.replace(_KAGGLE_BASE,
                                  table_sizes=CRITEO_KAGGLE_TABLE_SIZES)
DLRM_TERABYTE = dataclasses.replace(_TERABYTE_BASE,
                                    table_sizes=CRITEO_TERABYTE_TABLE_SIZES)
DLRM_DCNV2 = dataclasses.replace(_DCNV2_BASE,
                                 table_sizes=CRITEO_1TB_MLPERF_TABLE_SIZES,
                                 multi_hot=MLPERF_DCNV2_HOTNESS)


def scaled(cfg, max_rows: int = 100_000):
    """Shrink table cardinalities (keeping relative skew) for emulation."""
    top = max(cfg.table_sizes)
    sizes = tuple(max(4, min(n, int(max_rows * n / top)) if n > 100 else n)
                  for n in cfg.table_sizes)
    return dataclasses.replace(cfg, table_sizes=sizes,
                               name=cfg.name + f"-scaled{max_rows}")
