"""A configuration's model comes from its family module
(``bench/families/<family>.py``): DLRM's weights, rows and counts are
bitwise what the harness gave before they moved there, a family that is
new files alone resolves, weights, rows and counts, and an unknown family
fails cleanly."""
import copy
import hashlib
import json
import re
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import families, flops, gen, harness, layers, weights

BENCH = Path(__file__).resolve().parents[1]
SEEDS = (2 ** 31 + 11, 2 ** 40 + 3)


def _cfg(path):
    return json.loads((BENCH / path).read_text())


CONFIGS = {"rm1-chip": _cfg("configs/rm1-chip.json"),
           "tiny": _cfg("tests/data/tiny.json")}
# RM2 V0's widths (repro.configs.rm2), as test_bench_counts keeps them
CONFIGS["rm2"] = dict(CONFIGS["rm1-chip"], name="rm2", num_tables=400,
                      avg_pooling=40, bottom_mlp=[2048, 2048, 128],
                      top_mlp=[16384, 16384, 8192, 4096, 1])


def _hash(*arrays) -> str:
    m = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        m.update(str((a.shape, a.dtype.str)).encode())
        m.update(a.tobytes())
    return m.hexdigest()[:16]


# Taken from the harness before the move (weights._leaves, gen.make_rows
# with the traffic's pooling_sigma and alpha, and the counts of flops.py,
# at the same arguments).
PINNED = {
    "rm1-chip": dict(
        leaves="b31ad36d60028246",
        rows=["36181af6cfa0ab64", "581ea760a29f110d"],
        rows_zipf=["88be8522452e1a7d", "b96a5fbefd8f2b7f"],
        dense_flops_per_row={"bottom_mlp": 589824, "proj": 13107200,
                             "interaction": 1081600, "top_mlp": 7930368},
        dense_weight_bytes=17260036, dense_activation_bytes=26280192,
        bag_bytes={"rows": 632098304.0, "indices": 16384000.0,
                   "pooled": 26214400.0},
        pooling_flops=158024576.0, model_flops=1611400064.0),
    "tiny": dict(
        leaves="f31071e49322ab32",
        rows=["8ccd074d4bb36aec", "6ca244ea873cf936"],
        rows_zipf=["8e1aa7624f624bcb", "1585244fb702c7e3"],
        dense_flops_per_row={"bottom_mlp": 2048, "proj": 1024,
                             "interaction": 800, "top_mlp": 7488},
        dense_weight_bytes=19780, dense_activation_bytes=37120,
        bag_bytes={"rows": 79012288.0, "indices": 8192.0,
                   "pooled": 32768.0},
        pooling_flops=19753072.0, model_flops=20480112.0,
        weights=["7de2f17c3eacbce9", "d5d00de74f6302ba"]),
    "rm2": dict(
        leaves="df1f9ef96ca3821c",
        rows=["5bd40f94897bd9a6", "1f3164f99268245c"],
        rows_zipf=["b4e214e9bfc28dde", "2af81fd2b92d1081"],
        dense_flops_per_row={"bottom_mlp": 9961472, "proj": 6553600,
                             "interaction": 1081600, "top_mlp": 944775168},
        dense_weight_bytes=1909772804, dense_activation_bytes=13172992,
        bag_bytes={"rows": 632098304.0, "indices": 4096000.0,
                   "pooled": 13107200.0},
        pooling_flops=158024576.0, model_flops=61749822336.0),
}
# request sizes and the plans of both traffic mixes, as the harness drew them
# (the open mix at the rate it then had: the plan pins the generator)
PINNED_SIZES = ["d74fa4135dffc286", "c96ba45a1f3b9efe"]
PINNED_PLANS = {"rm1.backlog": ["06d56c34ed719a7c", "9dcc0949cb4c7d75"],
                "rm1.open": ["7b68658fbd3dde14", "0f5d4fe597701fb8"]}
PINNED_TRAFFIC = {"rm1.open": {"rate_qps": 2.5}}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_dlrm_leaves_and_counts_unchanged(name):
    """Every parameter's path, shape and scale, in the order
    ``weights.make`` draws them (so every array drawn from a seed), and
    every count."""
    cfg, pin = CONFIGS[name], PINNED[name]
    fam = families.family(cfg)
    leaves = repr(tuple(fam.leaves(cfg))).encode()
    assert _hash(np.frombuffer(leaves, np.uint8)) == pin["leaves"]
    assert fam.dense_flops_per_row(cfg) == pin["dense_flops_per_row"]
    assert fam.dense_weight_bytes(cfg) == pin["dense_weight_bytes"]
    assert fam.dense_activation_bytes(cfg, 64) == pin["dense_activation_bytes"]
    assert fam.bag_bytes(cfg, 64, 1234567) == pin["bag_bytes"]
    assert flops.pooling_flops(cfg, 1234567) == pin["pooling_flops"]
    assert flops.model_flops(cfg, 64, 1234567) == pin["model_flops"]


@pytest.mark.parametrize("name", sorted(PINNED))
@pytest.mark.parametrize("kind, sigma, alpha", [("rows", 0.3, 0.0),
                                                ("rows_zipf", 0.5, 1.05)])
def test_dlrm_rows_unchanged(name, kind, sigma, alpha):
    cfg = CONFIGS[name]
    traffic = {"pooling_sigma": sigma, "alpha": alpha}
    for seed, want in zip(SEEDS, PINNED[name][kind]):
        got = families.family(cfg).make_rows(cfg, 16, gen.rng_for(seed, 0),
                                             traffic)
        assert _hash(*got) == want


def test_tiny_weights_unchanged():
    cfg = CONFIGS["tiny"]
    for seed, want in zip(SEEDS, PINNED["tiny"]["weights"]):
        w = weights.make(cfg, seed)
        leaves = sorted((jax.tree_util.keystr(p), np.asarray(x)) for p, x in
                        jax.tree_util.tree_leaves_with_path(w))
        assert _hash(*[x for _, x in leaves]) == want


@pytest.mark.parametrize("workload", sorted(PINNED_PLANS))
def test_plans_unchanged(workload):
    traffic = dict(harness.resolve(workload).traffic,
                   **PINNED_TRAFFIC.get(workload, {}))
    for seed, want in zip(SEEDS, PINNED_SIZES):
        sizes = gen.query_sizes(np.random.default_rng(seed), 500, 64.0, 1.0,
                                4096)
        assert _hash(sizes) == want
    for seed, want in zip(SEEDS, PINNED_PLANS[workload]):
        if traffic["kind"] == "open":
            plan = gen.open_schedule(traffic, seed, 51.0)
        else:
            chunks = gen.backlog_chunks(traffic, seed)
            plan = [p for _ in range(3) for p in next(chunks)]
        assert _hash(np.array([(p.offset, p.size, p.arrival)
                               for p in plan])) == want


# A family of new files only: two tables of uneven size, a fixed bag length
# per table, and a dense tower of one MLP over [dense, pooled...].
STUB = '''
    import numpy as np
    from bench.flops import F32, matmul_flops
    from bench.weights import mlp_leaves


    def model_config(cfg):
        raise NotImplementedError("no program serves this family")


    def _dims(cfg):
        T, D = len(cfg["rows"]), cfg["embed_dim"]
        return [cfg["num_dense_features"] + T * D] + cfg["mlp"]


    def leaves(cfg):
        D = cfg["embed_dim"]
        mlp = mlp_leaves(_dims(cfg))
        return ([(("tables", f"t{t}"), (r, D), 0.5)
                 for t, r in enumerate(cfg["rows"])]
                + [(("mlp", n), s, sc) for n, s, sc in mlp])


    def make_rows(cfg, n, rng, traffic):
        T, P = len(cfg["rows"]), max(cfg["bag"])
        dense = rng.standard_normal((n, cfg["num_dense_features"]),
                                    dtype=np.float32)
        idx = np.full((n, T, P), -1, np.int32)
        for t, (r, b) in enumerate(zip(cfg["rows"], cfg["bag"])):
            idx[:, t, :b] = rng.integers(0, r, size=(n, b))
        return dense, idx


    def dense_flops_per_row(cfg):
        return {"mlp": matmul_flops(_dims(cfg))}


    def dense_weight_bytes(cfg):
        d = _dims(cfg)
        return sum(a * b + b for a, b in zip(d[:-1], d[1:])) * F32


    def dense_activation_bytes(cfg, rows):
        return rows * (_dims(cfg)[0] + 1) * F32


    def bag_bytes(cfg, rows, valid_slots):
        D, P = cfg["embed_dim"], max(cfg["bag"])
        return {"rows": float(valid_slots) * D * F32,
                "indices": float(rows) * len(cfg["rows"]) * P * 4,
                "pooled": float(rows) * len(cfg["rows"]) * D * F32}
'''


def test_family_of_new_files_only(tmp_path, monkeypatch):
    """A configuration whose family is a module on the family search path
    (here a temporary directory): the harness resolves its cell, makes its
    weights and rows, and counts its work, with no edit to a file of the
    benchmark."""
    (tmp_path / "stubfam.py").write_text(textwrap.dedent(STUB))
    monkeypatch.setattr(families, "__path__",
                        [*families.__path__, str(tmp_path)])
    cfg = {"name": "stub-chip", "family": "stubfam", "reference": "stub",
           "rows": [3, 40, 1000], "bag": [1, 5, 2], "embed_dim": 8,
           "num_dense_features": 4, "mlp": [16, 1], "dtype": "float32",
           "matmul_precision": "highest", "limits": {"score_gap": 1e-5}}
    (tmp_path / "stub-chip.json").write_text(json.dumps(cfg))
    spec = copy.deepcopy(harness.load_spec())
    spec["configs"].append({"name": "stub-chip",
                            "file": str(tmp_path / "stub-chip.json")})
    spec["workloads"].append({"name": "stub.backlog", "config": "stub-chip",
                              "traffic": "ddr4.backlog", "chips": 1})
    cell = harness.resolve("stub.backlog", spec)
    assert "stubfam" in families.known()

    w = weights.make(cell.config, SEEDS[0])
    assert [w["tables"][f"t{t}"].shape for t in range(3)] == [
        (3, 8), (40, 8), (1000, 8)]
    assert w["mlp"]["w0"].shape == (4 + 3 * 8, 16)
    again = weights.make(cell.config, SEEDS[0])
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree_util.tree_leaves(w), jax.tree_util.tree_leaves(again)))

    payloads = harness.Payloads(cell, SEEDS[1])
    n = cell.traffic["pool_rows"]
    assert payloads.dense.shape == (n, 4) and payloads.idx.shape == (n, 3, 5)
    assert ((payloads.idx >= 0).sum(axis=(0, 2)) == [n, 5 * n, 2 * n]).all()
    assert (payloads.idx.max(axis=(0, 2)) < [3, 40, 1000]).all()

    valid = float((payloads.idx[:64] >= 0).sum())
    assert flops.model_flops(cell.config, 64, valid) == (
        64 * 2 * (28 * 16 + 16) + valid * 8)
    fam = families.family(cell.config)
    assert fam.bag_bytes(cell.config, 64, valid)["rows"] == valid * 32
    reading = harness.Reading(cell, flops.peaks("TPU v5 lite"), rows=64,
                              valid_slots=int(valid), batches=1,
                              serve_s=1e-3, window_s=1e-3)
    assert layers.mfu(reading) == pytest.approx(
        100 * flops.model_flops(cell.config, 64, valid) / 1e-3 / 197e12)
    with pytest.raises(NotImplementedError):
        harness.build_engine(cell, w)


@pytest.mark.parametrize("name", ["rm9", "../dlrm", "", None])
def test_unknown_family_fails_cleanly(name):
    cfg = dict(CONFIGS["tiny"], family=name)
    with pytest.raises(harness.BenchError, match=re.escape(
            f"configuration 'tiny' names unknown family {name!r}; "
            "known: dlrm")):
        weights.make(cfg, 1)


@pytest.mark.parametrize("module", ["harness", "weights", "gen", "flops",
                                    "layers", "calibrate"])
def test_harness_reads_no_family_key(module):
    """What a configuration's model is stays in its family module: the
    harness's own modules name none of DLRM's keys."""
    text = (BENCH / f"{module}.py").read_text()
    for key in ("interaction_proj", "avg_pooling", "rows_per_table",
                "bottom_mlp", "top_mlp", "num_tables"):
        assert key not in text, (module, key)
