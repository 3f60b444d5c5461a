"""The pooled vectors stay on the device from the bag kernels to the dense
step.

``ClusterEngine._execute`` dispatches each MN's bag call without waiting
for it, assembles the (B, T, D) block on the device in one jitted call
(``gather_pooled``) and fetches only the scores.  These tests pin:

- the scores equal, bitwise, those of a host assembly rebuilt here (each
  MN's ``_mn_pool`` output copied to numpy into ``pooled[:, cols]``, then
  the model's dense step), over pool mixes, a fleet, a CN cache, an MN
  that holds none of a model's tables, the reference bags, per-table bag
  lengths, and after a mid-stage ``FailMN`` re-issue;
- ``pooled_device_batches`` counts every ``_execute``;
- nothing but the (B,) scores moves from the device to the host during
  one ``_execute``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import dcnv2, rm1
from repro.core import embedding_manager as em
from repro.data.queries import QueryDist, dlrm_batch
from repro.models.dlrm import DLRMModel
from repro.serving.cluster import ClusterConfig, ClusterEngine
from repro.serving.engine import Request
from repro.serving.scenario import FailMN

CFG = rm1.CONFIG.replace(
    name="rm1-pooled",
    dlrm=rm1.DLRMConfig(num_tables=5, rows_per_table=48, embed_dim=8,
                        avg_pooling=4, num_dense_features=8,
                        bottom_mlp=(16, 8), top_mlp=(32, 16, 1)),
)
CFG_B = CFG.replace(name="rm1-pooled-b",
                    dlrm=dataclasses.replace(CFG.dlrm, num_tables=3,
                                             avg_pooling=6))
ArrayImpl = type(jnp.zeros(()))     # the concrete class of jax.Array
MODEL, MODEL_B, MODEL_DCN = (DLRMModel(CFG), DLRMModel(CFG_B),
                             DLRMModel(dcnv2.REDUCED))


def _engine(mix):
    """An engine for one pool mix, and the configs of its fleet."""
    cc = dict(n_cn=2, m_mn=4, batch_size=8, n_replicas=2)
    if mix == "ddr":
        return ClusterEngine(MODEL, MODEL.init(0), ClusterConfig(**cc)), [CFG]
    if mix == "ddr-nmp":
        return ClusterEngine(MODEL, MODEL.init(0), ClusterConfig(
            mn_types=["ddr_mn"] * 2 + ["nmp_mn"] * 2, **cc)), [CFG]
    if mix == "ddr-cache":
        return ClusterEngine(MODEL, MODEL.init(0), ClusterConfig(
            cache_mb=0.01, **cc)), [CFG]
    if mix == "reference":
        return ClusterEngine(MODEL, MODEL.init(0), ClusterConfig(
            use_kernel=False, **cc)), [CFG]
    if mix == "dcn":
        return ClusterEngine(MODEL_DCN, MODEL_DCN.init(0),
                             ClusterConfig(**cc)), [dcnv2.REDUCED]
    if mix == "fleet":
        pa, pb = MODEL.init(0), MODEL_B.init(1)
        return ClusterEngine(MODEL, pa, ClusterConfig(**cc), fleet=[
            ("a", MODEL, pa), ("b", MODEL_B, pb)]), [CFG, CFG_B]
    assert mix == "idle-mn"      # six MNs, five tables, one copy of each
    cc.update(m_mn=6, n_replicas=1)
    return ClusterEngine(MODEL, MODEL.init(0), ClusterConfig(**cc)), [CFG]


MIXES = ["ddr", "ddr-nmp", "ddr-cache", "fleet", "idle-mn", "reference",
         "dcn"]


def _calls(eng, task, model):
    off, tm = eng._tbl_off[model], eng._tbl_count[model]
    shards = em.shard_assignment(eng.alloc, eng.routing, eng.T, eng.m_mn,
                                 task)
    return [(j, [t for t in tids if off <= t < off + tm])
            for j, tids in enumerate(shards)]


def _host_scores(eng, task, dense, idx, model=0):
    """The scores of the host assembly: each MN's pooled block fetched to
    numpy and copied into its columns, the block uploaded to the dense
    step."""
    off = eng._tbl_off[model]
    pooled = np.zeros((dense.shape[0], eng._tbl_count[model], eng.D),
                      np.float32)
    for j, mtids in _calls(eng, task, model):
        if not mtids:
            continue
        cols = [t - off for t in mtids]
        sub = idx[:, cols, :max(eng._bag_len[t] for t in mtids)]
        pooled[:, cols, :] = np.asarray(eng._mn_pool(j, mtids, sub))
    return np.asarray(eng._dense_steps[model](
        eng._fleet_params[model], jnp.asarray(dense), jnp.asarray(pooled)))


def _bitwise_equal(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return a.shape == b.shape and np.array_equal(a.view(np.uint32),
                                                 b.view(np.uint32))


def _requests(cfg, n, seed, model=0, rid0=0):
    rng = np.random.RandomState(seed)
    sizes = QueryDist(mean_size=4.0, max_size=12).sample(rng, n)
    reqs = []
    for i, s in enumerate(sizes):
        b = dlrm_batch(cfg, int(s), rng)
        reqs.append(Request(rid0 + i, {"dense": b["dense"],
                                       "indices": b["indices"]},
                            int(s), 0.004 * i, model=model))
    return reqs


@pytest.mark.parametrize("mix", MIXES)
def test_execute_scores_match_host_assembly(mix):
    eng, cfgs = _engine(mix)
    rng = np.random.RandomState(11)
    calls = 0
    for model, cfg in enumerate(cfgs):
        for task in range(eng.n_cn):
            b = dlrm_batch(cfg, 8, rng)
            want = _host_scores(eng, task, b["dense"], b["indices"], model)
            got, _, _ = eng._execute(task, b["dense"], b["indices"],
                                     model=model)
            calls += 1
            assert _bitwise_equal(got, want), (mix, model, task)
    assert eng.pooled_device_batches == calls
    if mix == "idle-mn":
        assert any(not m for _, m in _calls(eng, 0, 0))


@pytest.mark.parametrize("mix", MIXES)
def test_pooled_device_batches_counts_batches_served(mix):
    eng, cfgs = _engine(mix)
    reqs = []
    for model, cfg in enumerate(cfgs):
        reqs += _requests(cfg, 12, 5 + model, model=model,
                          rid0=100 * model)
    res, stats = eng.serve(sorted(reqs, key=lambda r: r.arrival))
    assert len(res) == len(reqs)
    assert stats.reissues == 0
    assert eng.batches_seen > 0
    assert eng.pooled_device_batches == eng.batches_seen


def test_reissue_after_failmn_matches_host_assembly(monkeypatch):
    """Every ``_execute`` of a served stream, the re-issue of the batch
    a mid-stage failure hit included, scores as the host assembly does
    under the routing it ran with."""
    eng, _ = _engine("ddr")
    eng.mn_bw = [1.0] * eng.m_mn          # seconds-long scans: the failure
    orig = ClusterEngine._execute         # lands inside an MN stage
    checked = []

    def execute(self, task, dense, idx, model=0):
        want = _host_scores(self, task, dense, idx, model)
        out = orig(self, task, dense, idx, model)
        checked.append(_bitwise_equal(out[0], want))
        return out
    monkeypatch.setattr(ClusterEngine, "_execute", execute)
    res, stats = eng.serve(_requests(CFG, 16, 3),
                           events=[FailMN(0.5, mn=0)])
    assert stats.reissues >= 1
    assert 0 in eng.dead
    assert checked and all(checked)
    assert len(checked) == eng.batches_seen + stats.reissues
    assert eng.pooled_device_batches == len(checked)


@pytest.mark.parametrize("mix", ["ddr", "ddr-nmp", "fleet", "reference"])
def test_execute_fetches_only_the_scores(monkeypatch, mix):
    """During one ``_execute`` no device array is converted to numpy but
    the (B,) scores: the pooled vectors never visit the host."""
    eng, cfgs = _engine(mix)
    model = len(cfgs) - 1
    b = dlrm_batch(cfgs[model], 8, np.random.RandomState(2))
    eng._execute(0, b["dense"], b["indices"], model=model)   # compile
    fetched = []

    def recording(fn):
        def wrapper(x, *a, **k):
            if isinstance(x, jax.Array):
                fetched.append(tuple(x.shape))
            return fn(x, *a, **k)
        return wrapper
    value = ArrayImpl._value
    monkeypatch.setattr(np, "asarray", recording(np.asarray))
    monkeypatch.setattr(np, "array", recording(np.array))
    monkeypatch.setattr(jax, "device_get", recording(jax.device_get))
    monkeypatch.setattr(ArrayImpl, "_value",
                        property(recording(value.fget)))
    scores, _, _ = eng._execute(0, b["dense"], b["indices"], model=model)
    monkeypatch.undo()
    assert fetched == [(8,)]
    assert scores.shape == (8,)
