"""Work counts of bench/work.py against hand counts."""

import json
import os

import pytest

from bench import gen, harness, work

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as fh:
        return json.load(fh)


def _widths(name):
    return harness.Parts(_config(name)).widths


@pytest.mark.parametrize("name", ["dlrm-criteo-vocab131k"])
def test_dlrm_flops_per_row_hand_count(name):
    macs = work.dlrm_forward_macs_per_row(_widths(name))
    # bottom 16x512 + 512x256 + 256x128
    assert macs["bottom"] == 8192 + 131072 + 32768 == 172032
    # Gram matrix of 27 vectors of width 128
    assert macs["interaction"] == 27 * 27 * 128 == 93312
    # top_in = 128 + 26*27/2 = 479: 479x1024 + 1024x1024 + 1024x512
    # + 512x256 + 256x1
    assert macs["top"] == 490496 + 1048576 + 524288 + 131072 + 256 == 2194688
    assert macs["total"] == 2460032
    assert work.dlrm_train_flops_per_row(_widths(name)) == 14760192


@pytest.mark.parametrize("name", ["dlrm-criteo-vocab131k"])
def test_etl_bytes_per_batch_hand_count(name):
    config = _config(name)
    b = work.etl_bytes_per_batch(config, gen.load_mix("zipf1.3-closed"),
                                 harness.Parts(config).etl.table_capacities(
                                     config))
    # raw: label 4 + 13 dense x 4 + 26 sparse x 8 hex bytes = 264 B/row
    assert b["raw"] == 65536 * 264
    # packed: dense 16 x 4 + sparse 32 x 4 + label 4 = 196 B/row
    assert b["packed"] == 65536 * 196
    # one vocabulary shared by the 26 columns: 131,072 int32 slots
    assert b["tables"] == 131072 * 4
    assert b["total"] == 30670848


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v99 imaginary")
    assert work.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
