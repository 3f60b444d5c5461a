"""The DLRM-DCNv2 family (``families/dlrm_dcn.py``): the chip
configuration holds MLPerf's published widths with its rows cut to the
row cap, its weights, rows and counts are pinned, and a small DCN
configuration served through ``ClusterEngine.serve`` on the CPU agrees
with the plain reference (``references/dlrm_dcn.py``) on an all-DDR and a
DDR+NMP pool, where the three-pass control does not."""
import copy
import dataclasses
import hashlib
import json
import time
from pathlib import Path

import numpy as np
import pytest

from bench import gen, harness
from bench.families import family

BENCH = Path(__file__).resolve().parents[1]
SEEDS = (2 ** 31 + 11, 2 ** 40 + 3)
CHIP = json.loads((BENCH / "configs/dcnv2-chip.json").read_text())
TINY = "bench/tests/data/tiny-dcn.json"

# MLPerf DLRM-DCNv2's run command
PUBLISHED_ROWS = [40000000, 39060, 17295, 7424, 20265, 3, 7122, 1543, 63,
                  40000000, 3067956, 405282, 10, 2209, 11938, 155, 4, 976,
                  14, 40000000, 40000000, 40000000, 590152, 12973, 108, 36]
MULTI_HOT = [3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12,
             100, 27, 10, 3, 1, 1]


def _hash(*arrays) -> str:
    m = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        m.update(str((a.shape, a.dtype.str)).encode())
        m.update(a.tobytes())
    return m.hexdigest()[:16]


def test_chip_config_is_published_with_rows_capped():
    cap = CHIP["row_cap"]
    assert cap == 2 ** 19
    assert CHIP["published"]["num_embeddings_per_feature"] == PUBLISHED_ROWS
    assert CHIP["num_embeddings_per_feature"] == [min(r, cap)
                                                  for r in PUBLISHED_ROWS]
    assert sum(CHIP["num_embeddings_per_feature"]) == 4_196_496
    assert CHIP["multi_hot_sizes"] == MULTI_HOT and sum(MULTI_HOT) == 214
    assert (CHIP["num_dense_features"], CHIP["embedding_dim"]) == (13, 128)
    assert CHIP["dense_arch_layer_sizes"] == [512, 256, 128]
    assert CHIP["over_arch_layer_sizes"] == [1024, 1024, 512, 256, 1]
    assert (CHIP["dcn_num_layers"], CHIP["dcn_low_rank_dim"]) == (3, 512)
    assert CHIP["reduced"] == ["num_embeddings_per_feature"]


def test_chip_config_is_the_programs():
    """The family builds the program's own DLRM-DCNv2 chip share."""
    from repro.configs import dcnv2
    got = family(CHIP).model_config(CHIP).dlrm
    want = dcnv2.CHIP_SHARE.dlrm
    for key in ("rows", "bags", "embed_dim", "num_dense_features",
                "bottom_mlp", "top_mlp", "interaction", "dcn_num_layers",
                "dcn_low_rank_dim", "cross_width"):
        assert getattr(got, key) == getattr(want, key), key
    assert got.cross_width == 27 * 128


def test_chip_leaves_pinned():
    leaves = family(CHIP).leaves(CHIP)
    shapes = {p: s for p, s, _ in leaves}
    assert [shapes[("embed", f"t{t}")][0] for t in range(26)] == \
        CHIP["num_embeddings_per_feature"]
    assert shapes[("cross", "v0")] == (3456, 512)
    assert shapes[("cross", "w2")] == (512, 3456)
    assert shapes[("top", "w0")] == (3456, 1024)
    dense = sum(int(np.prod(s)) for p, s, _ in leaves if p[0] != "embed")
    assert dense == 16_044_545
    assert dense * 4 == family(CHIP).dense_weight_bytes(CHIP) == 64_178_180
    scales = {p: sc for p, _, sc in leaves}
    assert scales[("embed", "t20")] == 0.1
    assert scales[("top", "w0")] == pytest.approx((2 / 3456) ** 0.5)
    assert scales[("top", "w4")] == pytest.approx(256 ** -0.5)
    assert _hash(np.frombuffer(repr(tuple(leaves)).encode(), np.uint8)) == \
        "bf7037159e2ef440"


def test_chip_rows_full_bags_pinned():
    fam = family(CHIP)
    rows, bags = CHIP["num_embeddings_per_feature"], MULTI_HOT
    got = []
    for seed in SEEDS:
        dense, idx = fam.make_rows(CHIP, 16, gen.rng_for(seed, 0),
                                   {"alpha": 0.0})
        assert dense.shape == (16, 13) and dense.dtype == np.float32
        assert idx.shape == (16, 26, 100) and idx.dtype == np.int32
        for t, (r, p) in enumerate(zip(rows, bags)):
            assert (idx[:, t, :p] >= 0).all() and (idx[:, t, :p] < r).all()
            assert (idx[:, t, p:] == -1).all()
        assert ((idx >= 0).sum(axis=(1, 2)) == 214).all()
        got.append(_hash(dense, idx))
    assert got == ["3751bf6433dec4ae", "f092c89ec6fbe01c"]


def test_chip_counts_by_hand():
    fam = family(CHIP)
    # bottom 2(13*512 + 512*256 + 256*128); cross 3 layers x (3456 -> 512
    # -> 3456); top 2(3456*1024 + 1024*1024 + 1024*512 + 512*256 + 256)
    assert fam.dense_flops_per_row(CHIP) == {
        "bottom_mlp": 340_992, "cross": 21_233_664, "top_mlp": 10_486_272}
    assert sum(fam.dense_flops_per_row(CHIP).values()) == 32_060_928
    assert fam.dense_activation_bytes(CHIP, 2048) == 2048 * (13 + 3328 + 1) * 4
    assert fam.bag_bytes(CHIP, 2048, 2048 * 214) == {
        "rows": 2048 * 214 * 512.0, "indices": 2048 * 214 * 4.0,
        "pooled": 2048 * 26 * 512.0}


def test_dcn_cell_resolves():
    cell = harness.resolve("dcnv2.backlog")
    assert cell.config["name"] == "dcnv2-chip" and cell.chips == 1
    assert cell.pool["batch_size"] == 2048 and cell.pool["mn_types"] == [
        "ddr_mn"] * 4
    assert cell.traffic["chunk_rows"] == 8 * cell.pool["batch_size"]
    assert {m["name"] for m in cell.per_layer} == {
        f"{m}.dcn" for m in (
            "bag_roofline", "dense_roofline", "bag_walk_fill", "idle_share",
            "host_ms_per_batch", "mfu", "bag_slot_fill", "scatter_idle_ms",
            "gather_idle_ms", "bookkeeping_idle_ms")}
    assert {m["name"] for m in cell.end_to_end} == {"rows_per_s", "setup_s"}


@pytest.mark.parametrize("counters, want", [
    ({"bag_slots_valid": 300, "bag_slots_walked": 400}, 75.0),
    ({"bag_slots_valid": 0, "bag_slots_walked": 0}, None),
    ({"bag_slots_valid": 300}, None),
])
def test_bag_walk_fill_reads_the_walked_counter(counters, want):
    """A program without the ``bag_slots_walked`` counter reads nothing."""
    cell = harness.resolve("dcnv2.backlog")
    reading = harness.Reading(cell, {}, rows=1, valid_slots=1, batches=1,
                              serve_s=1.0, window_s=1.0, counters=counters)
    assert harness.load_reader("bag_walk_fill.dcn")(reading) == want


# ------------------------------------------------- served on the CPU
def _tiny_cell(mn_types):
    spec = copy.deepcopy(harness.load_spec())
    spec["configs"].append({"name": "tiny-dcn", "file": TINY})
    spec["workloads"].append({"name": "tiny.dcn", "config": "tiny-dcn",
                              "traffic": "ddr4-2k.backlog-16k", "chips": 1})
    cell = harness.resolve("tiny.dcn", spec)
    return dataclasses.replace(
        cell, pool=dict(cell.pool, batch_size=16, mn_types=mn_types),
        traffic=dict(cell.traffic, pool_rows=64, mean_size=8.0, max_size=32,
                     chunk_rows=64))


@pytest.mark.parametrize("mn_types", [["ddr_mn"] * 4,
                                      ["ddr_mn"] * 2 + ["nmp_mn"] * 2])
def test_tiny_dcn_served_matches_reference(mn_types):
    """Uneven rows (3 to 1,000) and bags (1 to 12) through the served
    path, Pallas bags in interpret mode: every sampled score within the
    configuration's limit of the reference's, no request failed."""
    cell = _tiny_cell(mn_types)
    out = harness.run(cell, SEEDS[0], 0.3, False, time.perf_counter(),
                      on_chip=False)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["score_gap"]["value"] <= \
        cell.config["limits"]["score_gap"]


def test_tiny_dcn_traced_run_reads_every_dcn_metric():
    """A ``--trace 1`` run with dcnv2.backlog's per-layer readers: none
    raises, and those that need no device op read a number (the CPU
    trace holds no device op, so the roofline and idle shares read
    nothing)."""
    cell = dataclasses.replace(
        _tiny_cell(["ddr_mn"] * 4),
        per_layer=harness.resolve("dcnv2.backlog").per_layer)
    out = harness.run(cell, SEEDS[1], 0.3, True, time.perf_counter(),
                      on_chip=False)
    assert out["correct"], out["checks"]
    got = out["metrics"]
    assert set(got) == {"mfu.dcn", "bag_slot_fill.dcn", "bag_walk_fill.dcn",
                        "host_ms_per_batch.dcn", "scatter_idle_ms.dcn",
                        "gather_idle_ms.dcn", "bookkeeping_idle_ms.dcn"}
    assert got["bag_walk_fill.dcn"]["value"] == pytest.approx(100.0)
    assert 0.0 < got["mfu.dcn"]["value"] < 100.0


def test_dcn_mfu_counts_the_family_and_the_adds():
    """mfu.dcn: (rows x the family's dense FLOPs + valid slots x D) over
    the time in serve(), against the bf16 peak."""
    cell = harness.resolve("dcnv2.backlog")
    reading = harness.Reading(cell, {"bf16_flops_per_s": 1e12}, rows=10,
                              valid_slots=2140, batches=1, serve_s=2.0,
                              window_s=2.0)
    want = 100.0 * (10 * 32_060_928 + 2140 * 128) / 2.0 / 1e12
    assert harness.load_reader("mfu.dcn")(reading) == pytest.approx(want)


def test_tiny_dcn_control_fails():
    cell = _tiny_cell(["ddr_mn"] * 4)
    limit = cell.config["limits"]["score_gap"]
    for seed in (SEEDS[1], 5, 77):
        payloads = harness.Payloads(cell, seed)
        dense, idx = payloads.rows([gen.Part(8 * r, 8, 0.0)
                                    for r in range(8)])
        w = harness.weights_for(cell, seed)
        want = harness.reference_scores(cell.config, w, dense, idx)
        low = harness.reference_scores(cell.config, w, dense, idx, passes=3)
        assert harness.score_gap(low, want) > limit
