"""The benchmark's operation and byte counts against hand counts of RM1
and RM2, and its table of peaks."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import flops
from bench.families import family

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def _mean_valid(cfg):
    """Mean valid slots per bag of the generator's pooling lengths."""
    _, idx = family(cfg).make_rows(cfg, 64, np.random.default_rng(0),
                                   {"pooling_sigma": 0.3, "alpha": 0.0})
    return float((idx >= 0).sum()) / (64 * cfg["num_tables"])


def test_rm1_flops_per_row():
    cfg = _cfg("rm1-chip")
    parts = family(cfg).dense_flops_per_row(cfg)
    valid = _mean_valid(cfg) * cfg["num_tables"]
    assert parts["proj"] == pytest.approx(13.1e6, rel=0.01)
    assert parts["top_mlp"] == pytest.approx(7.9e6, rel=0.01)
    assert flops.pooling_flops(cfg, valid) == pytest.approx(5.9e6, rel=0.02)
    assert flops.model_flops(cfg, 1, valid) == pytest.approx(28.6e6,
                                                             rel=0.01)


# RM2 V0 (repro.configs.rm2), whose hand counts the yardstick keeps: 400
# tables at pooling 40 and a GFLOP-class top MLP; no cell serves it
RM2 = dict(_cfg("rm1-chip"), name="rm2", num_tables=400, avg_pooling=40,
           bottom_mlp=[2048, 2048, 128], top_mlp=[16384, 16384, 8192, 4096, 1])


def test_rm2_dense_weights_and_flops():
    cfg = RM2
    valid = _mean_valid(cfg) * cfg["num_tables"]
    assert family(cfg).dense_weight_bytes(cfg) == pytest.approx(1.91e9, rel=0.005)
    assert family(cfg).dense_flops_per_row(cfg)["top_mlp"] == pytest.approx(
        945e6, rel=0.005)
    assert flops.model_flops(cfg, 1, valid) == pytest.approx(964e6,
                                                             rel=0.005)


def test_rm1_valid_row_bytes_per_full_batch():
    cfg = _cfg("rm1-chip")
    valid = _mean_valid(cfg) * cfg["num_tables"] * 64
    got = family(cfg).bag_bytes(cfg, 64, valid)
    assert got["rows"] == pytest.approx(1.51e9, rel=0.01)
    assert got["indices"] == 64 * 800 * 80 * 4
    assert got["pooled"] == 64 * 800 * 128 * 4


def test_peaks_keyed_by_device_kind():
    v5e = flops.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks("TPU v9 imaginary")
