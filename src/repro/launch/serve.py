"""Serving driver: disaggregated DLRM scoring or LM generation.

  PYTHONPATH=src python -m repro.launch.serve --arch rm1 --requests 64
  PYTHONPATH=src python -m repro.launch.serve \
      --scenario examples/scenarios/failover_storm.json   # declarative
  PYTHONPATH=src python -m repro.launch.serve --arch rm1 --cluster \
      --cns 2 --mns 4 --fail-mn 1
  PYTHONPATH=src python -m repro.launch.serve --arch rm1 --cluster \
      --mns 4 --mn-type "2xddr_mn+2xnmp_mn"        # heterogeneous pool
  PYTHONPATH=src python -m repro.launch.serve --arch rm1 --cluster \
      --cns 3 --mns 6 --elastic              # diurnal resize schedule
  PYTHONPATH=src python -m repro.launch.serve --arch rm1 --cluster \
      --alpha 1.05 --cache-mb 64             # skewed stream + CN row cache
  PYTHONPATH=src python -m repro.launch.serve --arch rm1 --cluster \
      --arrival poisson --sla-p99-ms 60      # live traffic + SLA feedback
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --reduced

Cluster serving goes through the declarative scenario API
(``serving.scenario.run_scenario``): ``--scenario path.json`` runs a
scenario file directly, and the legacy flag combinations are kept as a
preset builder (`spec_from_flags`) that assembles the equivalent
``ScenarioSpec`` — one front door either way.
"""
from __future__ import annotations

import argparse
import sys

import numpy as np

from repro import compile_cache, configs
from repro.data.queries import QueryDist, dlrm_request_stream
from repro.models import registry
from repro.serving.autoscaler import Autoscaler, AutoscalerConfig
from repro.serving.cluster import parse_mn_types
from repro.serving.engine import DLRMServingEngine, LMServingEngine, Request
from repro.serving.scenario import (FailMN, ModelRef, Resize, ScenarioSpec,
                                    Topology, Workload, run_scenario)


def spec_from_flags(args) -> ScenarioSpec:
    """The legacy CLI flags, expressed as a ScenarioSpec — the ad-hoc
    flag combinations are now just a preset builder over the scenario
    API."""
    mn_types = tuple(parse_mn_types(args.mn_type, args.mns))
    if args.models:
        archs = [a.strip() for a in args.models.split(",") if a.strip()]
        models = tuple(ModelRef(arch=a, reduced=args.reduced,
                                init_seed=args.seed) for a in archs)
    else:
        models = (ModelRef(arch=args.arch, reduced=args.reduced,
                           init_seed=args.seed),)
    events = []
    if args.fail_mn is not None:
        events.append(FailMN(0.001 * args.requests / 2, mn=args.fail_mn))
    if args.elastic:
        # one diurnal day mapped onto the stream; the CLI pool sizes are
        # the peak the trough scales down from
        toy = Autoscaler(AutoscalerConfig(
            qps_per_cn=1.0 / args.cns, qps_per_mn=1.0 / args.mns,
            min_cn=1, min_mn=min(2, args.mns),
            max_cn=args.cns, max_mn=args.mns))
        events += [Resize(e.time_s, n_cn=e.n_cn, m_mn=e.m_mn)
                   for e in toy.plan(peak_load=0.95,
                                     duration_s=0.001 * args.requests,
                                     steps=8)]
    return ScenarioSpec(
        name="cli",
        description="scenario assembled from repro.launch.serve flags",
        models=models,
        topology=Topology(
            n_cn=args.cns, m_mn=args.mns, batch_size=args.batch,
            n_replicas=args.replicas, use_kernel=args.use_kernel,
            mn_types=mn_types, cache_mb=args.cache_mb,
            cache_policy=args.cache_policy,
            inflight_depth=args.inflight_depth,
            cn_router=args.cn_router,
            hedge_multiplier=args.hedge_multiplier),
        workload=Workload(requests=args.requests, mean_size=8.0,
                          max_size=4 * args.batch, alpha=args.alpha,
                          gap_s=0.001, seed=args.seed,
                          arrival=args.arrival,
                          burstiness=args.burstiness,
                          trace_path=args.trace),
        sla_p99_s=(args.sla_p99_ms / 1e3
                   if args.sla_p99_ms is not None else None),
        sla_mode=args.sla_mode,
        events=tuple(events),
    )


def _print_report(rep) -> None:
    """One renderer for both cluster entry points: the scenario report's
    own summary, prefixed by the scored-output line only the flags path
    has reason to surface."""
    if rep.results:
        scores = np.concatenate([r.outputs for r in rep.results])
        print(f"[serve] scored {rep.completed}/{rep.total} queries "
              f"({scores.size} samples), mean CTR {scores.mean():.4f}")
    else:
        print(f"[serve] scored 0/{rep.total} queries (empty stream)")
    for line in rep.summary():
        print(line)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="rm1")
    p.add_argument("--models", default=None, metavar="A,B",
                   help="comma list of archs to serve as a fleet on one "
                        "shared pool (cluster mode), e.g. 'rm1,rm2' — "
                        "overrides --arch; rates split evenly and "
                        "per-model stats report on the shared pool")
    p.add_argument("--reduced", action="store_true", default=True)
    p.add_argument("--full", dest="reduced", action="store_false")
    p.add_argument("--requests", type=int, default=32)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--decode-steps", type=int, default=8)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--scenario", default=None, metavar="PATH",
                   help="run a declarative scenario file "
                        "(examples/scenarios/*.json) through "
                        "run_scenario — ignores the other cluster flags")
    p.add_argument("--cluster", action="store_true",
                   help="serve across {n CN, m MN} via ClusterEngine")
    p.add_argument("--cns", type=int, default=2)
    p.add_argument("--mns", type=int, default=4)
    p.add_argument("--replicas", type=int, default=2)
    p.add_argument("--mn-type", default="ddr_mn",
                   help="memory-pool spec: one type for the whole pool "
                        "('nmp_mn'), a comma list, or counted groups "
                        "('2xddr_mn+2xnmp_mn')")
    p.add_argument("--fail-mn", type=int, default=None,
                   help="kill this MN mid-stream (cluster mode)")
    p.add_argument("--elastic", action="store_true",
                   help="follow a diurnal resize schedule mapped onto "
                        "the request stream (cluster mode): both pools "
                        "scale down toward the trough and back")
    p.add_argument("--alpha", type=float, default=0.0,
                   help="Zipf row-popularity skew of the query stream "
                        "(0 = uniform; production streams ~1.05)")
    p.add_argument("--cache-mb", type=float, default=0.0,
                   help="per-CN hot-row cache budget in MB (cluster mode; "
                        "0 disables)")
    p.add_argument("--inflight-depth", type=int, default=1,
                   help="max batches concurrently inside the MN stage "
                        "(1 = sequential clock, bitwise-identical to "
                        "the pre-pipeline model)")
    p.add_argument("--cache-policy", default="lru", choices=["lru", "lfu"],
                   help="hot-row cache eviction policy")
    p.add_argument("--cn-router", default="cpu_free",
                   choices=["cpu_free", "pipeline_free",
                            "least_outstanding"],
                   help="batch -> CN placement policy (cluster mode): "
                        "cpu_free routes on the preprocess core's "
                        "free_at (legacy, bitwise parity), pipeline_free "
                        "on the whole cpu/nic/gpu pipeline drain, "
                        "least_outstanding on fewest uncommitted "
                        "bookings")
    p.add_argument("--arrival", default="linear",
                   choices=["linear", "poisson", "bursty", "trace"],
                   help="arrival process of the request stream (cluster "
                        "mode; linear reproduces the historical evenly-"
                        "spaced stream byte-for-byte)")
    p.add_argument("--burstiness", type=float, default=4.0,
                   help="bursty arrivals: burst/lull rate swing factor "
                        "(>= 1; ignored by other processes)")
    p.add_argument("--trace", default=None, metavar="PATH",
                   help="JSON arrival-timestamp trace file "
                        "(requires --arrival trace)")
    p.add_argument("--sla-p99-ms", type=float, default=None,
                   help="p99 latency SLA in ms (cluster mode): enables "
                        "the feedback SLAController, which watches the "
                        "measured sliding-window p99 and emits live "
                        "Resize events to hold it under the target")
    p.add_argument("--sla-mode", default="coupled",
                   choices=["coupled", "decoupled"],
                   help="SLA controller scaling split (with --sla-p99-ms)"
                        ": coupled steps both pools in lockstep; "
                        "decoupled attributes each breach to the binding "
                        "pool and emits partial per-pool resizes")
    p.add_argument("--hedge-multiplier", type=float, default=0.0,
                   help="hedged re-issue of straggling MN scans: re-issue "
                        "on a replica once a scan exceeds this multiple "
                        "of its nominal time (0 disables)")
    p.add_argument("--no-kernel", dest="use_kernel", action="store_false",
                   default=True)
    args = p.parse_args(argv)
    compile_cache.enable()

    if args.scenario:
        spec = ScenarioSpec.load(args.scenario)
        rep = run_scenario(spec)
        if spec.description:
            print(f"[serve] scenario {spec.name!r}: {spec.description}")
        _print_report(rep)
        return 0

    cfg = (configs.get_reduced(args.arch) if args.reduced
           else configs.get_config(args.arch))
    model = registry.build(cfg)
    params = model.init(args.seed)
    rng = np.random.RandomState(args.seed)

    if cfg.family == "dlrm":
        if args.cluster:
            spec = spec_from_flags(args)
            if len(spec.models) > 1:
                # fleet specs build their own models (the single
                # prebuilt model/params pair can't cover the fleet)
                rep = run_scenario(spec)
            else:
                rep = run_scenario(spec, model=model, params=params)
            _print_report(rep)
        else:
            qd = QueryDist(mean_size=8.0, max_size=4 * args.batch,
                           alpha=args.alpha)
            reqs = [Request(*t) for t in
                    dlrm_request_stream(cfg, args.requests, seed=args.seed,
                                        dist=qd, gap_s=0.001)]
            engine = DLRMServingEngine(model, params, batch_size=args.batch,
                                       use_kernel=args.use_kernel)
            results = engine.serve(reqs)
            scores = np.concatenate([r.outputs for r in results])
            print(f"[serve] scored {len(results)} queries "
                  f"({scores.size} samples), mean CTR {scores.mean():.4f}")
    else:
        if args.cluster:
            print("[serve] --cluster only applies to dlrm archs; "
                  "running single-unit LM generation")
        engine = LMServingEngine(model, params, cache_len=128)
        toks = rng.randint(0, cfg.vocab_size, (2, 16)).astype(np.int32)
        extra = {}
        if cfg.family == "audio":
            extra["frames"] = rng.randn(
                2, cfg.encdec.encoder_seq, cfg.d_model).astype(np.float32)
        if cfg.family == "vlm":
            extra["images"] = rng.randn(
                2, cfg.vlm.num_patches, cfg.d_model).astype(np.float32)
        out = engine.generate(toks, steps=args.decode_steps, extra=extra)
        print(f"[serve] generated {out.shape[1]} tokens/seq for "
              f"{out.shape[0]} sequences: {out[0].tolist()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
