"""ClusterEngine: multi-unit routed serving + MN failure survival.

Ground truth for outputs is the model's own serve_step on each query's
full payload — the cluster's scatter/fused-pool/gather path must score
every query identically regardless of batching, routing, or failures.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import rm1
from repro.core.scheduler import Batcher, Query
from repro.data.queries import QueryDist, dlrm_batch
from repro.models.dlrm import DLRMModel
from repro.serving.cluster import ClusterConfig, ClusterEngine
from repro.serving.engine import Request

CFG = rm1.CONFIG.replace(
    name="rm1-test",
    dlrm=rm1.DLRMConfig(num_tables=6, rows_per_table=64, embed_dim=8,
                        avg_pooling=5, num_dense_features=8,
                        bottom_mlp=(16, 8), top_mlp=(32, 16, 1)),
)


@pytest.fixture(scope="module")
def model_and_params():
    model = DLRMModel(CFG)
    return model, model.init(0)


def make_requests(n, seed=0, mean_size=5.0, max_size=24):
    rng = np.random.RandomState(seed)
    sizes = QueryDist(mean_size=mean_size, max_size=max_size).sample(rng, n)
    reqs = []
    for i, s in enumerate(sizes):
        b = dlrm_batch(CFG, int(s), rng)
        reqs.append(Request(i, {"dense": b["dense"],
                                "indices": b["indices"]},
                            int(s), 0.005 * i))
    return reqs


def direct_scores(model, params, reqs):
    out = {}
    for r in reqs:
        batch = {"dense": jnp.asarray(r.payload["dense"]),
                 "indices": jnp.asarray(r.payload["indices"])}
        out[r.rid] = np.asarray(model.serve_step(params, batch))
    return out


def test_cluster_end_to_end(model_and_params):
    model, params = model_and_params
    reqs = make_requests(20)
    eng = ClusterEngine(model, params, ClusterConfig(
        n_cn=2, m_mn=4, batch_size=16, n_replicas=2))
    results, stats = eng.serve(reqs)
    assert stats.completed == len(reqs)
    assert sorted(r.rid for r in results) == list(range(len(reqs)))
    want = direct_scores(model, params, reqs)
    for r in results:
        assert r.outputs.shape == (reqs[r.rid].size,)
        np.testing.assert_allclose(r.outputs, want[r.rid],
                                   atol=1e-5, rtol=1e-5)
    # every query saw a positive modeled latency
    assert all(r.latency > 0 for r in results)
    # greedy routing kept the MN pool roughly balanced
    assert stats.imbalance < 2.0


def test_cluster_replication_places_tables(model_and_params):
    model, params = model_and_params
    eng = ClusterEngine(model, params, ClusterConfig(
        n_cn=2, m_mn=4, n_replicas=2))
    for tid, reps in eng.alloc.replicas.items():
        assert len(reps) == 2
    # union of shards covers all tables
    covered = sorted({t for tids in eng._shard_tids for t in tids})
    assert covered == list(range(CFG.dlrm.num_tables))


def test_cluster_survives_mn_failure_mid_stream(model_and_params):
    """Kill one MN while queries are in flight: all queries must still
    complete, with outputs identical to the failure-free run, and no
    traffic may reach the dead MN afterwards."""
    model, params = model_and_params
    reqs = make_requests(20)
    cc = ClusterConfig(n_cn=2, m_mn=4, batch_size=16, n_replicas=2)

    clean = ClusterEngine(model, params, cc)
    res_clean, _ = clean.serve(reqs)
    want = {r.rid: r.outputs for r in res_clean}

    eng = ClusterEngine(model, params, cc)
    t_fail = 0.03                      # mid-stream: arrivals span 0..0.1
    res, stats = eng.serve(reqs, failures=[(t_fail, 1)])
    assert stats.failures == 1
    assert stats.reroutes >= 1 and stats.reinits == 0
    assert stats.completed == len(reqs)          # no dropped queries
    for r in res:
        np.testing.assert_allclose(r.outputs, want[r.rid],
                                   atol=1e-5, rtol=1e-5)
    assert 1 in eng.dead
    # post-failure routing never targets the dead MN
    for (task, tid), dest in eng.routing.routes.items():
        assert dest != 1


def test_cluster_reinit_when_last_replica_lost(model_and_params):
    """n_replicas=1: an MN failure loses tables entirely -> the engine
    re-initializes shards from params and keeps serving correctly."""
    model, params = model_and_params
    reqs = make_requests(12)
    eng = ClusterEngine(model, params, ClusterConfig(
        n_cn=2, m_mn=3, batch_size=16, n_replicas=1))
    lost_tables = list(eng._shard_tids[0])
    assert lost_tables                 # MN 0 held something
    res, stats = eng.serve(reqs, failures=[(0.02, 0)])
    assert stats.completed == len(reqs)
    assert stats.reinits == 1
    want = direct_scores(model, params, reqs)
    for r in res:
        np.testing.assert_allclose(r.outputs, want[r.rid],
                                   atol=1e-5, rtol=1e-5)


def test_cluster_kernel_matches_ref_path(model_and_params):
    model, params = model_and_params
    reqs = make_requests(8)
    cc = dict(n_cn=2, m_mn=4, batch_size=16, n_replicas=2)
    r_k, _ = ClusterEngine(model, params,
                           ClusterConfig(use_kernel=True, **cc)).serve(reqs)
    r_r, _ = ClusterEngine(model, params,
                           ClusterConfig(use_kernel=False, **cc)).serve(reqs)
    for a, b in zip(r_k, r_r):
        np.testing.assert_allclose(a.outputs, b.outputs,
                                   atol=1e-6, rtol=1e-6)


def test_cluster_latency_model_cross_validates(model_and_params):
    """The engine's virtual clock is built from the analytic stage model
    with measured G_S bytes — unloaded they must agree closely."""
    model, params = model_and_params
    eng = ClusterEngine(model, params, ClusterConfig(
        n_cn=2, m_mn=4, batch_size=16, n_replicas=2))
    eng.serve(make_requests(16))
    v = eng.validate_latency_model()
    assert 0.3 < v["ratio"] < 3.0


# ------------------------------------------------------ NMP memory nodes
MIX = ["ddr_mn", "ddr_mn", "nmp_mn", "nmp_mn"]


def test_parse_mn_types_specs():
    from repro.serving.cluster import parse_mn_types
    assert parse_mn_types("ddr_mn", 3) == ["ddr_mn"] * 3
    assert parse_mn_types("nmp_mn", 2) == ["nmp_mn"] * 2
    assert parse_mn_types("ddr_mn,nmp_mn", 2) == ["ddr_mn", "nmp_mn"]
    assert parse_mn_types("2xddr_mn+2xnmp_mn", 4) == MIX
    with pytest.raises(ValueError):
        parse_mn_types("2xddr_mn", 4)          # wrong pool size
    with pytest.raises(ValueError):
        parse_mn_types("cn_1g", 1)             # not a memory node


def test_cluster_hetero_bitwise_and_gather_savings(model_and_params):
    """Acceptance: a mixed DDR+NMP cluster scores bitwise-identically to
    the all-DDR baseline while NMP-sourced shards move strictly fewer
    gather bytes at strictly lower modeled G_S time."""
    model, params = model_and_params
    reqs = make_requests(20)
    cc = dict(n_cn=2, m_mn=4, batch_size=16, n_replicas=2)
    eng_d = ClusterEngine(model, params, ClusterConfig(**cc))
    res_d, st_d = eng_d.serve(reqs)
    eng_m = ClusterEngine(model, params, ClusterConfig(mn_types=MIX, **cc))
    res_m, st_m = eng_m.serve(reqs)

    want = {r.rid: r.outputs for r in res_d}
    assert st_m.completed == len(reqs)
    for r in res_m:
        assert np.array_equal(r.outputs, want[r.rid])   # bitwise

    # NMP shards ship pooled Fsum vectors: strictly fewer fabric bytes
    # than the rows they scan; DDR shards ship exactly what they scan
    for j, t in enumerate(st_m.mn_types):
        if st_m.mn_access_bytes[j] == 0:
            continue
        if "nmp" in t:
            assert st_m.mn_gather_bytes[j] < st_m.mn_access_bytes[j]
        else:
            assert st_m.mn_gather_bytes[j] == st_m.mn_access_bytes[j]
    assert sum(st_m.mn_gather_bytes) < sum(st_d.mn_gather_bytes)

    # modeled per-MN G_S time: the NMP shards finish strictly faster
    # even though node-type-aware routing steers them MORE traffic
    ddr_stage = [eng_m.mn_stage_s[j] for j in range(4) if not eng_m.mn_nmp[j]]
    nmp_stage = [eng_m.mn_stage_s[j] for j in range(4) if eng_m.mn_nmp[j]]
    assert max(nmp_stage) < min(ddr_stage)
    nmp_mem = sum(st_m.mn_access_bytes[j] for j in range(4)
                  if eng_m.mn_nmp[j])
    ddr_mem = sum(st_m.mn_access_bytes[j] for j in range(4)
                  if not eng_m.mn_nmp[j])
    assert nmp_mem > ddr_mem

    # all-NMP pool: strictly lower batch-gating MN stage than all-DDR
    eng_n = ClusterEngine(model, params, ClusterConfig(
        mn_type="nmp_mn", **cc))
    res_n, st_n = eng_n.serve(reqs)
    for r in res_n:
        assert np.array_equal(r.outputs, want[r.rid])
    assert (eng_n._mn_stage_max_sum / eng_n._n_batches
            < eng_d._mn_stage_max_sum / eng_d._n_batches)


def test_cluster_hetero_replicas_span_classes(model_and_params):
    """With replication >= 2 in a mixed pool, every table keeps one copy
    in each node class (type-diverse replication)."""
    model, params = model_and_params
    eng = ClusterEngine(model, params, ClusterConfig(
        n_cn=2, m_mn=4, n_replicas=2, mn_types=MIX))
    for tid, reps in eng.alloc.replicas.items():
        classes = {("nmp" if eng.mn_nmp[j] else "ddr") for j in reps}
        assert classes == {"ddr", "nmp"}


def test_cluster_hetero_survives_mn_failure(model_and_params):
    """Killing a DDR MN in a mixed pool mid-stream re-routes its tables
    onto their NMP replicas with bitwise-identical outputs."""
    model, params = model_and_params
    reqs = make_requests(16)
    cc = ClusterConfig(n_cn=2, m_mn=4, batch_size=16, n_replicas=2,
                       mn_types=MIX)
    clean = ClusterEngine(model, params, cc)
    res_c, _ = clean.serve(reqs)
    eng = ClusterEngine(model, params, cc)
    res_f, stats = eng.serve(reqs, failures=[(0.03, 0)])
    assert stats.completed == len(reqs)
    assert stats.reroutes >= 1 and stats.reinits == 0
    want = {r.rid: r.outputs for r in res_c}
    for r in res_f:
        assert np.array_equal(r.outputs, want[r.rid])
    for (task, tid), dest in eng.routing.routes.items():
        assert dest != 0


def test_cluster_nmp_latency_model_regression(model_and_params):
    """Satellite: the executable all-NMP cluster's virtual-clock latency
    agrees with the analytic `nmp_mn` ServingUnitModel prediction.

    Full batches (query size == batch size) isolate the model from
    partial-batch scaling; stated tolerance: engine/analytic within
    [0.5, 2.0] end-to-end and the measured G_S+gather stage within
    [0.3, 2.0] of the analytic sparse+comm-out stages."""
    from repro.core.serving_unit import ServingUnitModel, UnitSpec
    model, params = model_and_params
    rng = np.random.RandomState(3)
    reqs = []
    for i in range(12):
        b = dlrm_batch(CFG, 16, rng)
        reqs.append(Request(i, {"dense": b["dense"],
                                "indices": b["indices"]}, 16, 0.005 * i))
    eng = ClusterEngine(model, params, ClusterConfig(
        n_cn=2, m_mn=4, batch_size=16, n_replicas=2, mn_type="nmp_mn"))
    eng.serve(reqs)
    assert all(eng.mn_nmp)
    # the engine's analytic reference IS the nmp_mn unit spec
    assert eng.unit_model.unit.mn_type == "nmp_mn"
    want = ServingUnitModel(model.cfg, UnitSpec(
        2, "cn_1g", 4, "nmp_mn")).stage_times(16).total()
    v = eng.validate_latency_model()
    assert v["analytic_s"] == pytest.approx(want)
    assert 0.5 < v["ratio"] < 2.0
    assert 0.3 < v["mn_stage_ratio"] < 2.0


def test_serve_deterministic_across_runs(model_and_params):
    """Seed standardization (issue #4 satellite): building the stream
    from `dlrm_request_stream(seed)` and the engine from
    `ClusterConfig.seed` twice must reproduce the *entire* ClusterStats
    byte-for-byte — scores, latencies, and every counter."""
    import dataclasses
    from repro.data.queries import QueryDist, dlrm_request_stream
    model, params = model_and_params

    def one_run():
        qd = QueryDist(mean_size=5.0, max_size=24, alpha=1.05)
        reqs = [Request(*t) for t in
                dlrm_request_stream(CFG, 14, seed=42, dist=qd,
                                    gap_s=0.005)]
        eng = ClusterEngine(model, params, ClusterConfig(
            n_cn=2, m_mn=4, batch_size=16, n_replicas=2, seed=42,
            cache_mb=0.01))
        res, st = eng.serve(reqs, failures=[(0.03, 1)])
        return res, st

    res_a, st_a = one_run()
    res_b, st_b = one_run()
    assert dataclasses.asdict(st_a) == dataclasses.asdict(st_b)
    for a, b in zip(res_a, res_b):
        assert a.rid == b.rid and a.latency == b.latency
        assert np.array_equal(a.outputs, b.outputs)


def test_batcher_parts_conservation():
    """Batch.parts records exactly each query's row contribution."""
    b = Batcher(batch_size=16)
    out = []
    sizes = [5, 40, 3, 3, 64, 1]
    for i, size in enumerate(sizes):
        out += b.offer(Query(i, float(i), size), float(i))
    out += [bt for bt in [b._form(99.0)] if bt.size]
    got = {}
    for bt in out:
        assert sum(n for _, n in bt.parts) == bt.size
        for q, n in bt.parts:
            got[q.qid] = got.get(q.qid, 0) + n
    assert got == {i: s for i, s in enumerate(sizes)}


@pytest.mark.parametrize("tables,mn_type,batch", [
    (16, "ddr_mn", 64),      # T a multiple of 8: every lane filled
    (13, "ddr_mn", 64),      # fused call pads T = 13 to 16 lanes
    (13, "nmp_mn", 60),      # NMP call pads B = 60 to 64 lanes
])
def test_bag_lanes_count_lane_padding(tables, mn_type, batch):
    """``bag_lanes`` counts the slots the bag kernels walk once the lane
    axis (tables of a fused call, bags of an NMP call) is padded to 8;
    it equals ``bag_slots`` when that axis is a multiple of 8."""
    cfg = CFG.replace(dlrm=rm1.DLRMConfig(
        num_tables=tables, rows_per_table=32, embed_dim=8, avg_pooling=5,
        num_dense_features=8, bottom_mlp=(16, 8), top_mlp=(16, 1)))
    model = DLRMModel(cfg)
    rng = np.random.RandomState(3)
    reqs = []
    for i, size in enumerate((batch // 2, batch - batch // 2)):
        b = dlrm_batch(cfg, size, rng)
        reqs.append(Request(i, {"dense": b["dense"],
                                "indices": b["indices"]}, size, 0.0))
    eng = ClusterEngine(model, model.init(0), ClusterConfig(
        n_cn=1, m_mn=1, batch_size=batch, n_replicas=1, mn_type=mn_type))
    _, stats = eng.serve(reqs)
    assert stats.completed == 2 and eng.batches_seen == 1
    P = cfg.dlrm.avg_pooling
    assert eng.bag_slots == batch * tables * P
    lanes_b = -(-batch // 8) * 8 if mn_type == "nmp_mn" else batch
    lanes_t = tables if mn_type == "nmp_mn" else -(-tables // 8) * 8
    assert eng.bag_lanes == lanes_b * lanes_t * P
    assert (eng.bag_lanes == eng.bag_slots) == (lanes_b * lanes_t
                                                == batch * tables)


def test_bag_slots_walked_equals_bag_slots_for_uniform_bags(
        model_and_params):
    """Every RM1 table's bag is as long as the index block is wide, so the
    kernels walk every slot of it."""
    model, params = model_and_params
    eng = ClusterEngine(model, params, ClusterConfig(
        n_cn=2, m_mn=4, batch_size=16, n_replicas=2))
    eng.serve(make_requests(8))
    assert eng.bag_slots_walked == eng.bag_slots > 0


@pytest.mark.parametrize("mn_types", [["ddr_mn"] * 4,
                                      ["ddr_mn"] * 2 + ["nmp_mn"] * 2])
def test_uneven_tables_served_at_their_own_rows_and_lengths(mn_types):
    """DLRM-DCNv2's reduced model (tables of 3 to 1,000 rows, bags of 1 to
    12, a cross network): each shard holds its tables at their own rows,
    each MN's index block is as wide as its longest bag, the kernels walk
    each table's length, and the scores match the model's own forward
    pass on the reference pooling."""
    from repro.configs import dcnv2
    cfg = dcnv2.REDUCED
    r = cfg.dlrm
    model = DLRMModel(cfg)
    params = model.init(0)
    rng = np.random.RandomState(6)
    reqs = []
    for i, size in enumerate((5, 11, 16, 3)):
        b = dlrm_batch(cfg, size, rng, pooling_sigma=None)
        reqs.append(Request(i, {"dense": b["dense"],
                                "indices": b["indices"]}, size, 0.0))
    eng = ClusterEngine(model, params, ClusterConfig(
        n_cn=2, m_mn=4, batch_size=8, n_replicas=2, mn_types=mn_types))
    res, stats = eng.serve(reqs)
    assert stats.completed == len(reqs)
    for j, tids in enumerate(eng._shard_tids):
        assert eng._shard_flat[j].shape[0] == sum(r.rows[t] for t in tids)
    # full bags in the 35 real rows; batches padded to 8 rows are walked
    assert eng.bag_slots_valid == 35 * sum(r.bags)
    assert eng.bag_slots_walked == eng.batches_seen * 8 * sum(r.bags)
    assert eng.bag_slots < eng.batches_seen * 8 * r.num_tables * r.bag_width
    want = {q.rid: np.asarray(jax.nn.sigmoid(model.forward(params, {
        "dense": jnp.asarray(q.payload["dense"]),
        "indices": jnp.asarray(q.payload["indices"])}))) for q in reqs}
    for out in res:
        np.testing.assert_allclose(out.outputs, want[out.rid],
                                   atol=1e-5, rtol=1e-5)


def test_fleet_shares_one_width_not_one_row_count(model_and_params):
    """Fleet members may hold tables of other row counts (a per-table
    pool); only the embedding width must agree.  The pool's slack per MN
    is its largest table."""
    model, params = model_and_params
    other = CFG.replace(name="rm1-short", dlrm=rm1.DLRMConfig(
        num_tables=3, rows_per_table=20, embed_dim=8, avg_pooling=5,
        num_dense_features=8, bottom_mlp=(16, 8), top_mlp=(32, 16, 1)))
    mb = DLRMModel(other)
    eng = ClusterEngine(model, params, ClusterConfig(
        n_cn=1, m_mn=2, batch_size=8), fleet=[("a", model, params),
                                              ("b", mb, mb.init(1))])
    assert [t.rows for t in eng.tables] == [64] * 6 + [20] * 3
    assert isinstance(eng.params["embed"], dict)
    total = sum(t.size_bytes for t in eng.tables)
    assert eng.capacities == [total + 64 * 8 * 4] * 2
