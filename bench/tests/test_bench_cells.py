"""The harness finds a cell through its files alone, and the copied
generators are seeded and deterministic."""
import copy
import json
import re

import numpy as np
import pytest

from bench import gen, harness
from bench.families import family

SPEC = harness.load_spec()


def test_every_cell_resolves_from_its_files():
    for w in SPEC["workloads"]:
        cell = harness.resolve(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.pool["mn_types"] and cell.traffic["kind"] in (
            "open", "backlog")
        names = {m["name"] for m in cell.end_to_end}
        assert {"setup_s", "rows_per_s"} <= names
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names
            assert callable(harness.load_reader(m["name"]))


def test_temporary_cell_from_existing_files():
    spec = copy.deepcopy(SPEC)
    spec["workloads"].append({"name": "tmp.nmp-open", "config": "rm1-chip",
                              "traffic": "ddr2-nmp2.open-poisson",
                              "chips": 1, "why": "test"})
    cell = harness.resolve("tmp.nmp-open", spec)
    assert cell.config["num_tables"] == 800
    assert cell.traffic["kind"] == "open"
    assert cell.pool["mn_types"] == ["ddr_mn"] * 2 + ["nmp_mn"] * 2
    # metrics that list their cells leave a cell they do not list alone
    assert [m["name"] for m in cell.end_to_end] == ["rows_per_s", "setup_s"]
    assert cell.per_layer == []


@pytest.mark.parametrize("change, match", [
    (lambda s: None, "unknown workload 'no.such-cell'"),
    (lambda s: s["workloads"].append(
        {"name": "no.such-cell", "config": "rm3", "traffic": "backlog",
         "chips": 1}), "unknown configuration 'rm3'"),
    (lambda s: s["workloads"].append(
        {"name": "no.such-cell", "config": "rm1-chip",
         "traffic": "ddr4.storm", "chips": 1}), "traffic 'storm': no file"),
    (lambda s: s["workloads"].append(
        {"name": "no.such-cell", "config": "rm1-chip",
         "traffic": "cxl8.backlog", "chips": 1}), "pool 'cxl8': no file"),
    (lambda s: s["workloads"].append(
        {"name": "no.such-cell", "config": "rm1-chip", "traffic": "backlog",
         "chips": 1}), "traffic 'backlog' is not <pool>.<mix>"),
])
def test_unknown_names_fail_cleanly(change, match):
    spec = copy.deepcopy(SPEC)
    change(spec)
    with pytest.raises(harness.BenchError, match=re.escape(match)):
        harness.resolve("no.such-cell", spec)


def test_unknown_reader_fails_cleanly():
    with pytest.raises(harness.BenchError, match="has no reader"):
        harness.load_reader("no_such_metric.open")


def test_poisson_arrivals_seeded_and_deterministic():
    def gaps(seed, n=2000):
        a = gen.Arrivals("poisson", 0.25, np.random.default_rng(seed))
        return np.array([a.next_gap() for _ in range(n)])
    a, b, c = gaps(7), gaps(7), gaps(8)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert a.mean() == pytest.approx(0.25, rel=0.1)
    with pytest.raises(ValueError):
        gen.Arrivals("linear", 0.25, np.random.default_rng(0))


def test_open_schedule_same_work_in_another_order():
    """Every seed gets the same sizes at the same arrival times; the seed
    moves which payload rows a request takes."""
    traffic = harness.resolve("rm1.open").traffic
    big = 2 ** 31 + 12345
    a = gen.open_schedule(traffic, big, 20.0)
    assert a == gen.open_schedule(traffic, big, 20.0)
    b = gen.open_schedule(traffic, 3, 20.0)
    assert [p.offset for p in a] != [p.offset for p in b]
    assert [p.size for p in a] == [p.size for p in b]
    arr = [p.arrival for p in a]
    assert arr == [p.arrival for p in b]
    assert arr == sorted(arr) and 0 < arr[0] and arr[-1] < 20.0
    assert len(arr) == pytest.approx(20.0 * traffic["rate_qps"], rel=0.3)
    assert all(0 <= p.offset <= traffic["pool_rows"] - p.size for p in a)


def test_backlog_chunks_hold_exact_rows():
    traffic = harness.resolve("rm1.backlog").traffic
    it1, it2 = (gen.backlog_chunks(traffic, 2 ** 33 + 5) for _ in range(2))
    for _ in range(4):
        c1, c2 = next(it1), next(it2)
        assert c1 == c2
        assert sum(p.size for p in c1) == traffic["chunk_rows"]
        assert all(p.arrival == 0.0 for p in c1)


def test_payload_rows_seeded():
    cfg = json.loads((harness.BENCH / "configs" / "rm1-chip.json")
                     .read_text())
    traffic = {"pooling_sigma": 0.3, "alpha": 0.0}
    d1, i1 = family(cfg).make_rows(cfg, 4, gen.rng_for(2 ** 31 + 1, 0),
                                   traffic)
    d2, i2 = family(cfg).make_rows(cfg, 4, gen.rng_for(2 ** 31 + 1, 0),
                                   traffic)
    assert np.array_equal(d1, d2) and np.array_equal(i1, i2)
    assert i1.shape == (4, 800, 80) and i1.dtype == np.int32
    assert i1.max() < cfg["rows_per_table"] and i1.min() >= -1
    # every bag keeps at least one row, padding only after the valid ones
    valid = i1 >= 0
    assert valid[..., 0].all()
    assert (np.diff(valid.astype(int), axis=-1) <= 0).all()


@pytest.mark.parametrize("q", [50, 90])
def test_latency_readers_take_nearest_rank(q):
    """``p50_ms.open``/``p90_ms.open`` read the nearest-rank percentile of
    every completed request's latency, and nothing where the run timed no
    request (backlog traffic)."""
    cell = harness.resolve("rm1.open")
    read = harness.load_reader(f"p{q}_ms.open")
    lat = [0.001 * (i + 1) for i in range(40)][::-1]
    reading = harness.Reading(cell, {}, rows=1, valid_slots=1, batches=1,
                              serve_s=1.0, window_s=1.0, latency_s=lat)
    assert read(reading) == pytest.approx({50: 20.0, 90: 36.0}[q])
    assert read(harness.Reading(cell, {}, 1, 1, 1, 1.0, 1.0)) is None
