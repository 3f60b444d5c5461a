"""The served path's own spans and counters, and ``bench/spans.py``'s
split of the host's time by them.

On the CPU at the tiny configuration (``data/tiny.json``, Pallas bags in
interpret mode): every step of a batch has its span, inside the batch's
span, with no two leaves of a batch overlapping, and the slot counters
agree with the harness's own count of the rows served.  Times read on the
CPU are never reported.  On a trace recorded on a TPU v5e: the split, the
names of the device modules and of the idle gaps."""
import copy
import dataclasses
import time
from pathlib import Path

import jax
import pytest
from jax.profiler import ProfileData

from bench import flops, gen, harness, layers, spans, weights
from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"
SEED = 2 ** 31 + 4242
CALLS = 2
COUNTERS = ("bag_slots", "bag_slots_valid")


def _tiny_cell(metrics=()):
    spec = copy.deepcopy(harness.load_spec())
    spec["configs"].append({"name": "tiny",
                            "file": "bench/tests/data/tiny.json"})
    spec["workloads"].append({"name": "tiny.t", "config": "tiny",
                              "traffic": "ddr2-nmp2.backlog", "chips": 1})
    for m in spec["per_layer"]:
        if m["name"] in metrics:
            m["workloads"].append("tiny.t")
    cell = harness.resolve("tiny.t", spec)
    return dataclasses.replace(
        cell, traffic=dict(cell.traffic, pool_rows=64, mean_size=8.0,
                           max_size=32, chunk_rows=128))


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Two backlog chunks served under the profiler on a DDR+NMP pool."""
    cell = _tiny_cell()
    engine = harness.build_engine(cell, weights.make(cell.config, SEED))
    payloads = harness.Payloads(cell, SEED)
    chunks = gen.backlog_chunks(cell.traffic, SEED)
    win, rid = harness.Window(), 0
    before = {k: getattr(engine, k) for k in COUNTERS}
    batches0 = engine.batches_seen
    tdir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(tdir)):
        for _ in range(CALLS):
            reqs = []
            for part in next(chunks):
                win.parts[rid] = part
                reqs.append(payloads.request(rid, part))
                rid += 1
            results, _ = engine.serve(reqs)
            win.record(results, 0.0)
    deltas = {k: getattr(engine, k) - before[k] for k in COUNTERS}
    found = spans.program_spans(
        ProfileData.from_file(str(tr.find_xplane(tdir))))
    return dict(cell=cell, win=win, payloads=payloads, deltas=deltas,
                batches=engine.batches_seen - batches0, spans=found)


def test_every_span_appears(served):
    names = {s[0] for s in served["spans"]}
    assert set(spans.LEAF_SPANS) | {"repro.serve", "repro.batch"} <= names
    serves = [s for s in served["spans"] if s[0] == "repro.serve"]
    assert len(serves) == CALLS
    assert all(s[3]["requests"] > 0 for s in serves)
    # per-MN spans name their MN; the pool's two NMP MNs say so
    scatter = [s[3] for s in served["spans"] if s[0] == "repro.scatter"]
    assert {st["mn"] for st in scatter} == {0, 1, 2, 3}
    assert {st["mn"] for st in scatter if st["nmp"]} == {2, 3}


def test_leaves_nest_in_their_batch_and_never_overlap(served):
    found = served["spans"]
    batches = [s for s in found if s[0] == "repro.batch"]
    assert len(batches) == served["batches"] > 0
    assert all({"bid", "rows", "task"} <= set(b[3]) for b in batches)
    serves = [s for s in found if s[0] == "repro.serve"]
    # a batch id is unique within its serve() call
    for _, lo, hi, _ in serves:
        bids = [b[3]["bid"] for b in batches if lo <= b[1] <= hi]
        assert sorted(bids) == list(range(len(bids)))
    by_batch = {b[1]: [] for b in batches}
    for name, s, e, stats in found:
        if name not in spans.LEAF_SPANS:
            continue
        assert any(lo <= s <= e <= hi for _, lo, hi, _ in serves), name
        inside = [b for b in batches if b[1] <= s and e <= b[2]]
        if name == "repro.stats" or (name == "repro.account"
                                     and "mn" not in stats):
            # the end-of-call fold and the start-of-call hot-table
            # refresh belong to the call, not to a batch
            assert not inside, name
            continue
        assert len(inside) == 1, (name, s)
        by_batch[inside[0][1]].append((s, e, name))
    for start, leaves in by_batch.items():
        leaves.sort()
        assert {n for _, _, n in leaves} >= {
            "repro.assemble", "repro.clock", "repro.route",
            "repro.scatter", "repro.gather", "repro.account",
            "repro.dense", "repro.complete"}, start
        for (_, e0, n0), (s1, _, n1) in zip(leaves, leaves[1:]):
            assert e0 <= s1, (start, n0, n1)


def test_slot_counters_match_the_rows_served(served):
    cfg = served["cell"].config
    win = served["win"]
    assert len(win.done) == len(win.parts)
    d = served["deltas"]
    assert d["bag_slots_valid"] == harness.valid_slots(win,
                                                       served["payloads"])
    # every table is pooled once per batch, padding rows included
    batch = served["cell"].pool["batch_size"]
    assert d["bag_slots"] == (served["batches"] * batch * cfg["num_tables"]
                              * cfg["avg_pooling"])


def test_kept_trace_splits_host_time(tmp_path):
    """A ``--trace 1`` run's kept trace splits into the three groups of
    leaves; its batches and host time per batch are the harness's own
    (CPU: no device plane, so all of a span counts idle)."""
    cell = _tiny_cell(["host_ms_per_batch.backlog"])
    kept = tmp_path / "run.xplane.pb"
    out = harness.run(cell, SEED, 0.3, True, time.perf_counter(),
                      on_chip=False, keep_trace=kept)
    assert out["correct"], out["checks"]
    host = out["metrics"]["host_ms_per_batch.backlog"]["value"]
    got = spans.split(spans.load(kept))
    assert got["host_ms_per_batch"] == pytest.approx(host)
    parts = [got[m] for m in spans.GROUPS]
    assert all(p > 0 for p in parts)
    assert 0 <= got["remainder_ms"] < host
    assert got["idle_gaps"] == []


def test_named_gaps_by_innermost_span():
    """A gap goes to the span that holds most of it to itself: a leaf
    before the batch or call that encloses it, those before the harness's
    span around the call; the harness's wait between calls keeps a gap
    that a leaf only touches."""
    t = tr.Trace(busy=[tr.merge([(0, 10), (20, 30), (40, 50), (60, 70),
                                 (80, 90), (95, 100), (200, 210)])],
                 spans=[("bench.serve", 0, 100), ("repro.serve", 5, 80),
                        ("repro.batch", 5, 60), ("repro.scatter", 10, 14),
                        ("repro.gather", 14, 20), ("repro.clock", 30, 40),
                        ("bench.wait", 100, 195), ("bench.serve", 195, 210),
                        ("repro.scatter", 195, 200)])
    assert sorted(spans.named_gaps(t, 0, 210)) == sorted([
        ("repro.gather", 10e-9), ("repro.clock", 10e-9),
        ("repro.batch", 10e-9), ("repro.serve", 10e-9),
        ("bench.serve", 5e-9), ("bench.wait", 100e-9)])
    assert spans.named_gaps(t, 0, 210, top=1) == [("bench.wait", 100e-9)]


def test_split_by_hand():
    """Each group's idle time is its spans' time less the device time
    inside them, per batch; the remainder is what no leaf holds."""
    t = tr.Trace(busy=[[(12, 20), (40, 45)]],
                 spans=[("bench.window", 0, 200), ("bench.serve", 0, 100),
                        ("repro.batch", 2, 48), ("repro.batch", 50, 95),
                        ("repro.assemble", 2, 4), ("repro.route", 4, 6),
                        ("repro.scatter", 6, 14), ("repro.gather", 14, 30),
                        ("repro.dense", 30, 46), ("repro.clock", 52, 60),
                        ("repro.complete", 60, 62), ("repro.stats", 96, 99)])
    got = spans.split(t)
    assert got["batches"] == 2
    assert got["host_ms_per_batch"] == pytest.approx(1e-6 * 87 / 2)
    assert got["scatter_idle_ms"] == pytest.approx(1e-6 * 8 / 2)
    assert got["gather_idle_ms"] == pytest.approx(1e-6 * 21 / 2)
    assert got["bookkeeping_idle_ms"] == pytest.approx(1e-6 * 15 / 2)
    assert got["remainder_ms"] == pytest.approx(1e-6 * 43 / 2)
    with pytest.raises(ValueError):
        spans.split(tr.Trace(spans=[("bench.window", 0, 1)]))


@pytest.fixture(scope="module")
def span_trace():
    """One second of rm1.nmp-backlog traced on a TPU v5e by the program
    with its own spans (bench/run.py --trace 1 --keep-trace): 512 rows in
    8 batches."""
    return spans.load(DATA / "rm1_nmp_backlog_spans_1s.xplane.pb")


def test_split_on_span_trace(span_trace):
    """The three groups hold at least 85% of the device-idle time inside
    serve(), which is the accepted reader's ``host_ms_per_batch``; the
    dense step and both bag modules are found by name."""
    got = spans.split(span_trace)
    assert got["batches"] == 8
    (lo, hi), = span_trace.span("bench.window")
    reading = harness.Reading(
        harness.resolve("rm1.nmp-backlog"), flops.peaks("TPU v5 lite"),
        rows=512, valid_slots=23550741, batches=8, serve_s=0.0,
        window_s=(hi - lo) / 1e9, trace=span_trace, lo=lo, hi=hi)
    assert got["host_ms_per_batch"] == pytest.approx(
        layers.host_ms_per_batch(reading))
    parts = [got[m] for m in spans.GROUPS]
    assert all(p > 0 for p in parts)
    assert 0.85 * got["host_ms_per_batch"] <= sum(parts) <= got[
        "host_ms_per_batch"]
    assert {n for n, _ in got["device_ops"]} == {
        "jit_embedding_bag_fused_flat", "jit_embedding_bag_nmp_flat",
        "jit_dense_step"}


def test_span_trace_gaps_named_by_program_spans(span_trace):
    (lo, hi), = span_trace.span("bench.window")
    gaps = spans.named_gaps(span_trace, lo, hi)
    assert len(gaps) == 10
    assert all(n.startswith("repro.") for n, _ in gaps), gaps


def test_accepted_readers_on_span_trace():
    """The harness's reduction keeps its own spans and the program's, and
    every per-layer reader of the cell reads the trace through it, the
    program's counters over the window given (8 batches of 64 rows, 800
    tables, 80 slots a bag)."""
    t = tr.load(DATA / "rm1_nmp_backlog_spans_1s.xplane.pb")
    assert {n for n, _, _ in t.spans} == {
        "bench.window", "bench.serve", "bench.assemble", "repro.serve",
        "repro.batch", *spans.LEAF_SPANS}
    cell = harness.resolve("rm1.nmp-backlog")
    (lo, hi), = t.span("bench.window")
    serve_s = sum(e - s for s, e in t.span("bench.serve")) / 1e9
    reading = harness.Reading(
        cell, flops.peaks("TPU v5 lite"), rows=512, valid_slots=23550741,
        batches=8, serve_s=serve_s, window_s=(hi - lo) / 1e9, trace=t,
        lo=lo, hi=hi, counters={"bag_slots": 8 * 64 * 800 * 80,
                                "bag_slots_valid": 23550741})
    for m in cell.per_layer:
        v = harness.load_reader(m["name"])(reading)
        assert v is not None and v > 0, m["name"]
        if m["unit"] == "%":
            assert v <= 100, (m["name"], v)


def test_span_readers_match_split(span_trace):
    """The result line's ``*_idle_ms`` readers give on the chip trace what
    ``split`` gives on it, and ``bag_slot_fill`` the share of the counters
    it is handed."""
    got = spans.split(span_trace)
    (lo, hi), = span_trace.span("bench.window")
    reading = harness.Reading(
        harness.resolve("rm1.nmp-backlog"), flops.peaks("TPU v5 lite"),
        rows=512, valid_slots=23550741, batches=8, serve_s=0.0,
        window_s=(hi - lo) / 1e9, trace=span_trace, lo=lo, hi=hi,
        counters={"bag_slots": 32768000, "bag_slots_valid": 23550741})
    for group in spans.GROUPS:
        v = harness.load_reader(f"{group}.backlog")(reading)
        assert v == pytest.approx(got[group], rel=1e-12), group
        assert v == pytest.approx(layers.span_idle_ms(reading, group))
    assert harness.load_reader("bag_slot_fill.backlog")(
        reading) == pytest.approx(100 * 23550741 / 32768000)
    # a reading that holds no spans or no counters reads nothing
    bare = dataclasses.replace(reading, trace=tr.Trace(), counters={})
    assert bare.spans == []
    assert all(layers.span_idle_ms(bare, g) is None for g in spans.GROUPS)
    assert layers.bag_slot_fill(bare) is None


def test_traced_run_reads_program_spans_and_counters(tmp_path):
    """A ``--trace 1`` run hands its readers the program's spans in the
    window and the change of every integer counter of the engine: the
    result line carries the slot fill the counters give and the split of
    its kept trace (CPU: no device plane, so all of a span counts
    idle)."""
    names = [f"{g}.backlog" for g in spans.GROUPS] + [
        "bag_slot_fill.backlog"]
    cell = _tiny_cell(names)
    kept = tmp_path / "run.xplane.pb"
    out = harness.run(cell, SEED + 1, 0.3, True, time.perf_counter(),
                      on_chip=False, keep_trace=kept)
    assert out["correct"], out["checks"]
    got = spans.split(spans.load(kept))
    for group in spans.GROUPS:
        assert out["metrics"][f"{group}.backlog"]["value"] == pytest.approx(
            got[group])
    fill = out["metrics"]["bag_slot_fill.backlog"]["value"]
    assert 0 < fill < 100
