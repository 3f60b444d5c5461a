"""The comparison that decides ``correct``, at a size a CPU test holds
(``data/tiny.json``, with the limit set from its own readings): a sound
run passes; the control (the reference at three-pass ``high`` precision
in the program's place) and a run with the served path broken underneath
fail.  The harness runs on the CPU here (``on_chip``
off: no look for a chip), with the Pallas bags in interpret mode."""
import copy
import dataclasses
import time

import numpy as np
import pytest

from bench import harness
from repro.serving.cluster import ClusterEngine

SEED = 2 ** 31 + 2024


def _tiny_cell(traffic):
    spec = copy.deepcopy(harness.load_spec())
    spec["configs"].append({"name": "tiny",
                            "file": "bench/tests/data/tiny.json"})
    spec["workloads"].append({"name": "tiny.t", "config": "tiny",
                              "traffic": f"ddr4.{traffic}", "chips": 1})
    cell = harness.resolve("tiny.t", spec)
    # a smaller payload pool and smaller requests for the small tables, a
    # faster open loop
    return dataclasses.replace(
        cell, traffic=dict(cell.traffic, pool_rows=64, mean_size=8.0,
                           max_size=32, rate_qps=40.0))


def _run(cell, seed=SEED):
    return harness.run(cell, seed, 0.5, False, time.perf_counter(),
                       on_chip=False)


@pytest.mark.parametrize("traffic", ["backlog", "open-poisson"])
def test_sound_run_is_correct(traffic):
    out = _run(_tiny_cell(traffic))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"


def test_three_pass_control_fails():
    cell = _tiny_cell("backlog")
    limit = cell.config["limits"]["score_gap"]
    for seed in (SEED, 5, 77):
        payloads = harness.Payloads(cell, seed)
        win = harness.Window()
        for rid in range(8):
            part = harness.gen.Part(8 * rid, 8, 0.0)
            win.parts[rid] = part
            win.done[rid] = (0.0, np.zeros(8, np.float32))
        dense, idx = payloads.rows(list(win.parts.values()))
        w = harness.weights_for(cell, seed)
        want = harness.reference_scores(cell.config, w, dense, idx)
        low = harness.reference_scores(cell.config, w, dense, idx,
                                       passes=3)
        assert harness.score_gap(low, want) > limit


def _alter_answer(monkeypatch):
    orig = ClusterEngine._execute

    def execute(self, *a, **k):
        scores, mem, gat = orig(self, *a, **k)
        scores = np.array(scores)
        scores[0] += 0.01
        return scores, mem, gat
    monkeypatch.setattr(ClusterEngine, "_execute", execute)


def _half_batch(monkeypatch):
    orig = ClusterEngine._execute

    def execute(self, task, dense, idx, model=0):
        h = dense.shape[0] // 2
        scores, mem, gat = orig(self, task, dense, idx, model)
        return np.concatenate([scores[:h], scores[:h]]), mem, gat
    monkeypatch.setattr(ClusterEngine, "_execute", execute)


def _no_exchange(monkeypatch):
    orig = ClusterEngine._mn_pool

    def mn_pool(self, j, tids, idx_sub):
        out = orig(self, j, tids, idx_sub)
        return out * 0.0 if j == self.m_mn - 1 else out
    monkeypatch.setattr(ClusterEngine, "_mn_pool", mn_pool)


@pytest.mark.parametrize("fault", [_alter_answer, _half_batch,
                                   _no_exchange])
def test_broken_served_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = _run(_tiny_cell("backlog"))
    assert not out["correct"]
    assert out["checks"]["score_gap"]["value"] > out["checks"][
        "score_gap"]["limit"]
