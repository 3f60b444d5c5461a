"""The trace reduction: interval arithmetic on hand-made intervals, and
the whole reduction on a trace recorded on a TPU v5e."""
from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"


def test_merge_overlap_gaps():
    m = tr.merge([(5, 7), (0, 2), (1, 3), (7, 8), (10, 11)])
    assert m == [(0, 3), (5, 8), (10, 11)]
    assert tr.overlap(m, 2, 10.5) == 1 + 3 + 0.5
    assert tr.gaps(m, -1, 12) == [(-1, 0), (3, 5), (8, 10), (11, 12)]
    assert tr.gaps(m, 1, 2) == []


def test_idle_gaps_named_by_host_span():
    t = tr.Trace(busy=[tr.merge([(0, 10), (30, 40), (45, 100)])],
                 spans=[("bench.serve", 0, 35), ("bench.wait", 40, 45)])
    assert t.span("bench.wait") == [(40, 45)]
    assert tr.idle_gaps(t, 0, 100, ("bench.serve", "bench.wait")) == [
        ("serve", 20e-9), ("wait", 5e-9)]
    assert tr.busy_s(t, 0, 100) == pytest.approx(75e-9)
    assert tr.busy_within_s(t, t.span("bench.serve")) == pytest.approx(
        15e-9)


def test_module_names_drop_run_ids():
    assert tr.module_name("jit_embedding_bag_fused_flat(17)") == \
        "jit_embedding_bag_fused_flat"
    assert tr.module_name("jit__lambda") == "jit__lambda"


@pytest.fixture(scope="module")
def chip_trace():
    """One second of rm1.nmp-backlog traced on a TPU v5e (bench/run.py
    --trace 1 --keep-trace): four MN bags per batch, two of them NMP."""
    return tr.load(DATA / "rm1_nmp_backlog_1s.xplane.pb")


def test_chip_trace_reduction(chip_trace):
    t = chip_trace
    assert len(t.busy) == 1
    (lo, hi), = t.span("bench.window")
    serve = t.span("bench.serve")
    assert serve and all(lo <= s <= e <= hi for s, e in serve)
    busy = tr.busy_s(t, lo, hi)
    assert 0 < busy < (hi - lo) / 1e9
    # every operation in the window ran while the harness was in serve()
    assert tr.busy_within_s(t, serve) == pytest.approx(busy, rel=1e-6)
    mods = tr.module_seconds(t, lo, hi)
    assert set(mods) == {"jit_embedding_bag_fused_flat",
                         "jit_embedding_bag_nmp_flat", "jit__lambda"}
    assert sum(mods.values()) <= busy * 1.001
    gaps = tr.idle_gaps(t, lo, hi, ("bench.serve", "bench.assemble"))
    assert gaps and {g[0] for g in gaps} <= {"serve", "assemble", "other"}
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps),
                                          reverse=True)


def test_readers_on_chip_trace(chip_trace):
    """Every per-layer reader of the cell finds its number in the chip
    trace (the run served 512 rows in 8 batches), and no share of a
    roofline or a peak passes 100%.  The trace predates the program's own
    spans, so the readers of those find nothing and say so."""
    from bench import flops, harness
    cell = harness.resolve("rm1.nmp-backlog")
    (lo, hi), = chip_trace.span("bench.window")
    serve_s = sum(e - s for s, e in chip_trace.span("bench.serve")) / 1e9
    slots = round(512 * 800 * 57.45)
    reading = harness.Reading(
        cell, flops.peaks("TPU v5 lite"), rows=512,
        valid_slots=slots, batches=8, serve_s=serve_s,
        window_s=(hi - lo) / 1e9, trace=chip_trace, lo=lo, hi=hi,
        counters={"bag_slots": 8 * 64 * 800 * 80, "bag_slots_valid": slots})
    assert reading.spans == []
    for m in cell.per_layer:
        v = harness.load_reader(m["name"])(reading)
        if m["source"] == "program_span":
            assert v is None, m["name"]
            continue
        assert v is not None and v > 0, m["name"]
        if m["unit"] == "%":
            assert v <= 100, (m["name"], v)
