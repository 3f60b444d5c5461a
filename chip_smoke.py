"""Chip smoke: serve RM1 at published widths through ClusterEngine on one TPU.

  python3 chip_smoke.py [--seed 0] [--requests 24]

Builds the chip-share RM1 (``repro.configs.rm1.CHIP_SHARE``: RM1 V0 widths,
4,096 rows per table) from ``--seed`` and serves one seeded stream through
the served path — ingress batcher -> router -> per-MN Pallas bag -> gather
-> DLRM dense tower, via ``run_scenario`` -> ``ClusterEngine.serve``:

* phase A on an all-DDR {2 CN, 4 MN} pool, batch 64, uniform rows;
* phase B on the same stream with 2 DDR + 2 NMP memory nodes.

Every query's scores are checked against ``model.forward(params, batch,
use_kernel=False)`` on the unsharded params, run on the chip; one batch is
pooled on every MN through the engine's own bag call and checked against
the jnp bag reference; the compiled bag of each MN type must hold a Mosaic
kernel (``tpu_custom_call``).  The lines before the last are informational.
The last line of stdout is ``{"ok": true, "device": {...}}``; without a TPU,
or on any failed check, the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Scores are CTRs in (0, 1).  The engine and the reference run the same
# dense tower at TPU default matmul precision, which rounds fp32 operands
# to bf16 (8 mantissa bits) on the MXU.  The bags sum slots in ascending
# order while the reference's jnp sum may associate differently, so a
# pooled value one fp32 ulp apart can round to the neighbouring bf16 value
# (a 2^-8 relative step on that operand) and move a score by far less than
# 1e-3 — the loosest bound admitted here.  The observed maximum is printed.
SCORE_ATOL = 1e-3
# pooled sums of <= 80 fp32 rows of N(0, 0.01^2) entries: association
# order moves them by a few ulp of values below 1
POOLED_ATOL = 1e-5
BATCH = 64


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _memory() -> str:
    """Device memory so far (None where the backend keeps no stats)."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return (f"bytes_in_use {stats.get('bytes_in_use')}, "
            f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")


class _CompileCounter:
    """Counts XLA backend compiles: a listener for JAX's own monitoring
    event, registered for the length of one ``run``."""

    def __init__(self):
        from jax._src import dispatch
        self.event = dispatch.BACKEND_COMPILE_EVENT
        self.n = 0

    def __call__(self, name, _secs, **_kw):
        if name == self.event:
            self.n += 1


def _pad_batch(payloads, batch):
    """Stack request rows into one (batch, ...) batch, padded as the
    ingress batcher pads (zero dense rows, all -1 indices)."""
    import numpy as np
    dense = np.concatenate([p["dense"] for p in payloads])[:batch]
    idx = np.concatenate([p["indices"] for p in payloads])[:batch]
    pad = batch - dense.shape[0]
    dense = np.concatenate([dense, np.zeros((pad,) + dense.shape[1:],
                                            dense.dtype)])
    idx = np.concatenate([idx, np.full((pad,) + idx.shape[1:], -1,
                                       idx.dtype)])
    return dense, idx


def _check_pooling(engine, params, idx):
    """Pool one (B, T, P) batch on every MN task 0 routes to, through the
    engine's own bag call; compare with the jnp bag references on the
    unsharded tables and compile each MN type's bag to read its HLO.
    Returns (max |pooled - ref|, bitwise vs slot-order ref, {type: has
    Mosaic kernel})."""
    import jax
    import jax.numpy as jnp
    from repro.core import embedding_manager as em
    from repro.kernels import ops, ref

    seq_ref = jax.jit(ref.embedding_bag_seq_ref)
    jnp_ref = jax.jit(ref.embedding_bag_ref)
    shards = em.shard_assignment(engine.alloc, engine.routing, engine.T,
                                 engine.m_mn, 0)
    worst, bitwise, kernel = 0.0, True, {}
    for j, tids in enumerate(shards):
        if not tids:
            continue
        sub = jnp.asarray(idx[:, tids, :])
        got = engine._mn_pool(j, tids, idx[:, tids, :])
        tables = params["embed"][jnp.asarray(tids)]
        worst = max(worst, float(jnp.max(jnp.abs(got - jnp_ref(tables,
                                                                sub)))))
        bitwise &= bool(jnp.array_equal(got, seq_ref(tables, sub)))
        kind = engine.mn_types[j]
        if kind not in kernel:
            bag = (ops.embedding_bag_nmp_flat if engine.mn_nmp[j]
                   else ops.embedding_bag_fused_flat)
            offsets = jnp.asarray([engine._shard_off[j][t] for t in tids],
                                  jnp.int32)
            hlo = bag.lower(engine._shard_flat[j], offsets,
                            sub).compile().as_text()
            kernel[kind] = "tpu_custom_call" in hlo
    return worst, bitwise, kernel


def run(cfg, seed: int, requests: int) -> dict:
    """Serve phases A and B for ``cfg`` and run every check; returns the
    measured differences and flags (``main`` decides pass or fail)."""
    import jax
    compiles = _CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        return _serve_and_check(cfg, seed, requests, compiles)
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)


def _serve_and_check(cfg, seed, requests, compiles):
    import jax
    import numpy as np
    from repro.models import registry
    from repro.serving.cluster import parse_mn_types
    from repro.serving.scenario import (ModelRef, ScenarioSpec, Topology,
                                        Workload, plan_workload,
                                        run_scenario)

    model = registry.build(cfg)
    t0 = time.perf_counter()
    params = model.init(seed)
    jax.block_until_ready(params)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    _log(f"{cfg.name}: params {nbytes} bytes, init "
         f"{time.perf_counter() - t0:.3f}s, {_memory()}")

    spec_a = ScenarioSpec(
        name="chip_smoke_ddr",
        model=ModelRef(arch="rm1", reduced=False, init_seed=seed),
        topology=Topology(n_cn=2, m_mn=4, batch_size=BATCH),
        workload=Workload(requests=requests, alpha=0.0, seed=seed))
    spec_b = dataclasses.replace(
        spec_a, name="chip_smoke_ddr_nmp",
        topology=dataclasses.replace(
            spec_a.topology,
            mn_types=tuple(parse_mn_types("2xddr_mn+2xnmp_mn", 4))))
    stream = plan_workload(spec_a, cfg)
    reqs = stream[0]

    reports = {}
    for label, spec in (("A", spec_a), ("B", spec_b)):
        n0, t0 = compiles.n, time.perf_counter()
        rep = run_scenario(spec, model=model, params=params, stream=stream)
        wall = time.perf_counter() - t0
        eng = rep.engine
        if not eng.cfg.use_kernel:
            raise RuntimeError("the served path must run the bag kernels")
        shard_bytes = sum(f.nbytes for f in eng._shard_flat)
        _log(f"phase {label} [{','.join(rep.mn_types)}]: "
             f"{rep.completed}/{rep.total} requests, wall {wall:.3f}s, "
             f"{compiles.n - n0} compiles, shard bytes {shard_bytes}, "
             f"{_memory()}")
        if label == "B":
            _, idx = _pad_batch([r.payload for r in reqs], BATCH)
            pooled_diff, pooled_bitwise, kernel = _check_pooling(
                eng, params, idx)
        rep.engine = eng = None           # free this pool's shards
        gc.collect()
        reports[label] = rep

    ref_step = jax.jit(lambda p, d, i: jax.nn.sigmoid(
        model.forward(p, {"dense": d, "indices": i}, use_kernel=False)))
    want = {}
    for r in reqs:
        d, i = _pad_batch([r.payload], BATCH)
        want[r.rid] = np.asarray(ref_step(params, d, i))[:r.size]
    score_diff = {
        label: max(float(np.max(np.abs(res.outputs - want[res.rid])))
                   for res in rep.results)
        for label, rep in reports.items()}
    out = {
        "completed": all(rep.completed == rep.total == len(reqs)
                         for rep in reports.values()),
        "score_diff": score_diff,
        "ab_bitwise": reports["B"].bitwise_equal(reports["A"]),
        "pooled_diff": pooled_diff,
        "pooled_bitwise_seq_ref": pooled_bitwise,
        "kernel_in_hlo": kernel,
        "compiles": compiles.n,
    }
    _log("checks " + json.dumps(out, sort_keys=True))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=24)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    from repro import compile_cache
    from repro.configs import rm1
    _log(f"device {dev.device_kind} x{len(devices)}, compile cache "
         f"{compile_cache.enable()}")

    t0 = time.perf_counter()
    res = run(rm1.CHIP_SHARE, args.seed, args.requests)
    _log(f"{_memory()}, total wall {time.perf_counter() - t0:.3f}s")

    failed = []
    if not res["completed"]:
        failed.append("not every request completed")
    for label, diff in res["score_diff"].items():
        if not diff <= SCORE_ATOL:
            failed.append(f"phase {label} scores off by {diff}")
    if not res["pooled_diff"] <= POOLED_ATOL:
        failed.append(f"pooled off by {res['pooled_diff']}")
    if not (res["kernel_in_hlo"] and all(res["kernel_in_hlo"].values())):
        failed.append(f"no Mosaic bag kernel: {res['kernel_in_hlo']}")
    if failed:
        print("chip_smoke: FAILED: " + "; ".join(failed), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
