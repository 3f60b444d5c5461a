"""The benchmark's harness: resolve a cell from its files, set it up from
the seed, drive ``ClusterEngine.serve`` for a window on the wall clock,
check the scores it returned against the plain reference, and print one
result line.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a
configuration, a pool and a traffic mix.  Its configuration is the file
that entry's ``configs`` item names; its ``traffic`` reads
``<pool>.<mix>``: the pool it is served on is ``pools/<pool>.json`` and
the mix ``traffic/<mix>.json``; its per-layer metrics are read by
``metrics/<metric>.py``.  Nothing here names a cell.

Timing is the harness's own: ``serve`` replays its request list on a
virtual clock and runs the real JAX work synchronously, so the harness
times what a caller of ``serve`` waits for.  ``open`` traffic hands every
request whose scheduled arrival has passed to one ``serve`` call, so the
ingress batcher decides how queued requests share batches, and times
each request from its scheduled arrival to the return of its call;
``backlog`` traffic serves queued chunks back to back.
"""
from __future__ import annotations

import functools
import gc
import importlib
import importlib.util
import json
import math
import numbers
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from bench import gen
from bench import layers
from bench import trace as trace_mod
from bench.errors import BenchError
from bench.families import family

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
REF_BLOCK = 32          # rows per reference call
SAMPLE_ROWS = 512       # rows the correctness sample aims for
SPANS = ("bench.serve", "bench.wait", "bench.assemble")


# ------------------------------------------------------------------ cells
@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    pool: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def load_spec(root: Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _read(path: Path, what: str) -> Dict:
    if not path.is_file():
        raise BenchError(f"{what}: no file {path}")
    return json.loads(path.read_text())


def resolve(name: str, spec: Optional[Dict] = None, root: Path = ROOT,
            bench_dir: Path = BENCH) -> Cell:
    """The cell ``name`` of ``spec`` (``BENCHMARK.json`` by default),
    built from its files alone."""
    spec = load_spec(root) if spec is None else spec
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: "
                         f"{', '.join(sorted(cells))}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise BenchError(f"workload {name!r} names unknown configuration "
                         f"{w['config']!r}")
    config = _read(root / configs[w["config"]]["file"],
                   f"configuration {w['config']!r}")
    pool_name, _, mix = w["traffic"].partition(".")
    if not mix:
        raise BenchError(f"workload {name!r}: traffic {w['traffic']!r} is "
                         f"not <pool>.<mix>")
    pool = _read(bench_dir / "pools" / f"{pool_name}.json",
                 f"pool {pool_name!r}")
    traffic = _read(bench_dir / "traffic" / f"{mix}.json",
                    f"traffic {mix!r}")
    largest = min(traffic["max_size"],
                  traffic.get("chunk_rows", traffic["max_size"]))
    if largest > traffic["pool_rows"]:
        raise BenchError(f"traffic {mix!r}: pool_rows "
                         f"{traffic['pool_rows']} cannot hold a request "
                         f"of {largest} rows")
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", [name])
                 and m["moves"] in reported]
    return Cell(name, int(w["chips"]), config, traffic, pool, e2e,
                per_layer)


def load_reader(metric: str, bench_dir: Path = BENCH) -> Callable:
    """``read(reading)`` of ``metrics/<metric>.py``."""
    path = bench_dir / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise BenchError(f"per-layer metric {metric!r} has no reader {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ----------------------------------------------------------- system setup
def build_engine(cell: Cell, params):
    from repro.models import registry
    from repro.serving.cluster import ClusterConfig, ClusterEngine
    p = cell.pool
    cc = ClusterConfig(n_cn=p["n_cn"], m_mn=p["m_mn"],
                       batch_size=p["batch_size"], max_wait_s=p["max_wait_s"],
                       n_replicas=p["n_replicas"],
                       mn_types=tuple(p["mn_types"]),
                       cache_mb=p["cache_mb"])
    return ClusterEngine(
        registry.build(family(cell.config).model_config(cell.config)),
        params, cc)


class CompileCounter:
    """Counts executables JAX compiled or loaded from the persistent
    cache (its backend-compile event, which wraps both), and of those the
    ones loaded (its cache-hit event), while registered."""

    def __init__(self):
        from jax._src import dispatch
        self.event = dispatch.BACKEND_COMPILE_EVENT
        self.n = self.hits = 0

    def __call__(self, name, _secs, **_kw):
        if name == self.event:
            self.n += 1

    def hit(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        jax.monitoring.register_event_listener(self.hit)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self)
        jax.monitoring.unregister_event_listener(self.hit)
        return False


class Payloads:
    """The seeded pool of distinct candidate rows requests are cut from."""

    def __init__(self, cell: Cell, seed: int):
        cfg, t = cell.config, cell.traffic
        self.dense, self.idx = family(cfg).make_rows(
            cfg, t["pool_rows"], gen.rng_for(seed, 0), t)

    def request(self, rid: int, part: gen.Part):
        from repro.serving.engine import Request
        sl = slice(part.offset, part.offset + part.size)
        return Request(rid, {"dense": self.dense[sl],
                             "indices": self.idx[sl]}, part.size,
                       part.arrival)

    def rows(self, parts: List[gen.Part]) -> Tuple[np.ndarray, np.ndarray]:
        sl = [np.arange(p.offset, p.offset + p.size) for p in parts]
        sel = np.concatenate(sl)
        return self.dense[sel], self.idx[sel]


# ----------------------------------------------------------------- window
@dataclass
class Window:
    parts: Dict[int, gen.Part] = field(default_factory=dict)
    done: Dict[int, Tuple[float, np.ndarray]] = field(default_factory=dict)
    serve_s: float = 0.0          # host seconds inside serve() calls
    longest_s: float = 0.0        # the longest serve() call
    calls: int = 0
    t_end: float = 0.0            # last completion, seconds from start
    late_s: List[float] = field(default_factory=list)

    def record(self, results, t_done: float) -> None:
        for r in results:
            self.done[r.rid] = (t_done, np.asarray(r.outputs))
        self.t_end = max(self.t_end, t_done)

    @property
    def rows_done(self) -> int:
        return sum(self.parts[rid].size for rid in self.done)


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _serve(engine, reqs, win: Window, t0: float) -> None:
    with _span("bench.serve"):
        s = time.perf_counter()
        results, _ = engine.serve(reqs)
        e = time.perf_counter()
    win.serve_s += e - s
    win.longest_s = max(win.longest_s, e - s)
    win.calls += 1
    win.record(results, e - t0)


def run_backlog(engine, payloads: Payloads, cell: Cell, seed: int,
                seconds: float) -> Window:
    win, rid = Window(), 0
    chunks = gen.backlog_chunks(cell.traffic, seed)
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        with _span("bench.assemble"):
            reqs = []
            for part in next(chunks):
                win.parts[rid] = part
                reqs.append(payloads.request(rid, part))
                rid += 1
        _serve(engine, reqs, win, t0)
    return win


def run_open(engine, payloads: Payloads, cell: Cell, seed: int,
             seconds: float) -> Window:
    schedule = gen.open_schedule(cell.traffic, seed, seconds)
    limit = seconds + cell.traffic["drain_s"]
    win = Window(parts=dict(enumerate(schedule)))
    k, n = 0, len(schedule)
    t0 = time.perf_counter()
    while k < n:
        now = time.perf_counter() - t0
        if now > limit:
            break                 # the rest never completes: failed
        if schedule[k].arrival > now:
            with _span("bench.wait"):
                time.sleep(schedule[k].arrival - now)
            continue
        with _span("bench.assemble"):
            j = k + 1
            while j < n and schedule[j].arrival <= now:
                j += 1
            reqs = [payloads.request(i, schedule[i]) for i in range(k, j)]
            win.late_s += [now - schedule[i].arrival for i in range(k, j)]
        _serve(engine, reqs, win, t0)
        k = j
    return win


def warm_up(engine, payloads: Payloads, cell: Cell,
            compiles: CompileCounter) -> int:
    """Serve two full batches at once until a call compiles nothing: the
    dispatcher routes them to CN 0 and CN 1, so every (CN task, MN) bag
    shape and the dense step run.  Returns the calls made."""
    span = cell.traffic["pool_rows"] - 8
    parts = [gen.Part(8 * i % span, 8, 0.0)
             for i in range(cell.pool["batch_size"] // 4)]
    for call in range(1, 5):
        before = compiles.n
        engine.serve([payloads.request(i, p) for i, p in enumerate(parts)])
        if compiles.n == before:
            return call
    raise BenchError("warm-up still compiles after 4 calls")


# ------------------------------------------------------------ correctness
def sample(win: Window, seed: int) -> List[int]:
    """Completed requests to check, drawn from the seed: the longest, then
    others in a seeded order until ``SAMPLE_ROWS`` rows are held."""
    done = sorted(win.done)
    if not done:
        return []
    longest = max(done, key=lambda r: (win.parts[r].size, -r))
    out, rows = [longest], win.parts[longest].size
    for rid in gen.rng_for(seed, 2).permutation(done):
        if rows >= SAMPLE_ROWS:
            break
        if int(rid) != longest:
            out.append(int(rid))
            rows += win.parts[int(rid)].size
    return out


def reference_scores(cfg: Dict, weights, dense: np.ndarray,
                     idx: np.ndarray, passes: int = 6) -> np.ndarray:
    """Scores of the given rows by the configuration's reference
    (``references/<reference>.py``), ``REF_BLOCK`` rows a call (the last
    block padded with empty rows); ``passes=3`` gives the control."""
    import jax.numpy as jnp
    ref = importlib.import_module(f"bench.references.{cfg['reference']}")
    n = dense.shape[0]
    pad = -n % REF_BLOCK
    dense = np.concatenate([dense, np.zeros((pad,) + dense.shape[1:],
                                            dense.dtype)])
    idx = np.concatenate([idx, np.full((pad,) + idx.shape[1:], -1,
                                       idx.dtype)])
    out = [np.asarray(ref.scores(weights, jnp.asarray(dense[i:i + REF_BLOCK]),
                                 jnp.asarray(idx[i:i + REF_BLOCK]),
                                 passes=passes), np.float32)
           for i in range(0, dense.shape[0], REF_BLOCK)]
    return np.concatenate(out)[:n]


def score_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Widest |score - reference| over the rows, as a share of the
    reference scores' standard deviation; inf where a score is missing
    or not finite."""
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return math.inf
    return float(np.max(np.abs(got.astype(np.float64) - want))
                 / np.std(want.astype(np.float64)))


def weights_for(cell: Cell, seed: int):
    """The reference's own copy of the weights, made again from the
    seed."""
    from bench import weights
    return weights.make(cell.config, seed)


def check(cell: Cell, seed: int, win: Window, payloads: Payloads,
          control: bool = False) -> Dict[str, float]:
    """Compare the sampled served scores with the reference; with
    ``control``, also the reference computed at ``high`` precision (three
    bfloat16 passes) in the program's place.  Runs after the program's
    state is freed."""
    rids = sample(win, seed)
    if not rids:
        return {"score_gap": math.inf, "rows": 0}
    parts = [win.parts[r] for r in rids]
    dense, idx = payloads.rows(parts)
    w = weights_for(cell, seed)
    want = reference_scores(cell.config, w, dense, idx)
    got = [win.done[r][1] for r in rids]
    sizes_ok = all(g.shape == (p.size,) for g, p in zip(got, parts))
    out = {"score_gap": (score_gap(np.concatenate(got), want) if sizes_ok
                         else math.inf),
           "rows": int(dense.shape[0])}
    if control:
        low = reference_scores(cell.config, w, dense, idx, passes=3)
        out["control_gap"] = score_gap(low, want)
    return out


# -------------------------------------------------------------- readings
@dataclass
class Reading:
    """What a per-layer reader may read of one traced run."""
    cell: Cell
    peak: Dict
    rows: int                 # real rows served in the window
    valid_slots: int          # valid bag slots in those rows
    batches: int              # engine.batches_seen over the window
    serve_s: float            # host seconds inside serve() calls
    window_s: float           # window start to last completion (host)
    trace: Optional[trace_mod.Trace] = None
    lo: float = 0.0           # the traced window on the trace's clock, ns
    hi: float = 0.0
    # change of each of the engine's integer counters over the window
    counters: Dict[str, int] = field(default_factory=dict)
    # each completed request's latency, seconds, where requests arrive on
    # a schedule (open traffic)
    latency_s: List[float] = field(default_factory=list)

    def modules(self) -> Dict[str, float]:
        return trace_mod.module_seconds(self.trace, self.lo, self.hi)

    @functools.cached_property
    def spans(self) -> List[Tuple[str, float, float]]:
        """The program's spans (``repro.*``) inside the traced window, as
        (name, start, end), by start."""
        if self.trace is None:
            return []
        return [(n, s, e) for n, s, e in self.trace.spans
                if n.startswith(trace_mod.PROGRAM_PREFIX)
                and self.lo <= s and e <= self.hi]


def counters(engine) -> Dict[str, int]:
    """Every public integer attribute of ``engine``: its counters (and
    its sizes, which a window leaves unchanged)."""
    return {k: int(v) for k, v in vars(engine).items()
            if not k.startswith("_") and isinstance(v, numbers.Integral)
            and not isinstance(v, bool)}


def valid_slots(win: Window, payloads: Payloads) -> int:
    counts = (payloads.idx >= 0).sum(axis=(1, 2))
    return int(sum(counts[p.offset:p.offset + p.size].sum()
                   for rid, p in win.parts.items() if rid in win.done))


# ------------------------------------------------------------------- run
def device_info(chips: int, on_chip: bool = True):
    import jax
    devices = jax.devices()
    dev = devices[0]
    if on_chip and dev.platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {dev.platform!r}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX found "
                         f"{len(devices)}")
    return devices


def configure_jax(cell: Cell, cache: bool = True) -> Optional[str]:
    """Set the configuration's matmul precision for the whole process
    (the program's steps and the reference alike) and, with ``cache``,
    turn on the persistent compilation cache for every program.  Returns
    the cache's directory."""
    import jax
    if cell.config["matmul_precision"] != "highest":
        raise BenchError("the reference holds float32 at 'highest' matmul "
                         "precision only")
    jax.config.update("jax_default_matmul_precision", "highest")
    if not cache:
        return None
    from repro import compile_cache
    cache_dir = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        on_chip: bool = True, keep_trace: Optional[Path] = None) -> Dict:
    """One run of ``cell``; returns the result line's object.  Tests pass
    ``on_chip=False`` to run on the CPU: no look for a TPU, no peaks, no
    persistent compilation cache."""
    import jax
    devices = device_info(cell.chips, on_chip)
    dev = devices[0]
    cache_dir = configure_jax(cell, on_chip)
    from bench import flops, weights
    peak = (flops.peaks(dev.device_kind) if on_chip
            else flops.peaks("TPU v5 lite"))
    with CompileCounter() as compiles:
        params = weights.make(cell.config, seed)
        jax.block_until_ready(params)
        engine = build_engine(cell, params)
        payloads = Payloads(cell, seed)
        calls = warm_up(engine, payloads, cell, compiles)
        _log(f"{cell.name}: device {dev.device_kind} x{len(devices)}, "
             f"cache {cache_dir}; set-up compiled or loaded {compiles.n} "
             f"executables, {compiles.hits} of them from the persistent "
             f"cache; {calls} warm-up calls")
        # set-up's objects leave the collector's view, so that a
        # collection inside the window walks only what the window made
        gc.collect()
        gc.freeze()
        batches0, compiles0 = engine.batches_seen, compiles.n
        tdir = None
        if trace:
            counts0 = counters(engine)
            tdir = Path(tempfile.mkdtemp(prefix="bench-trace-"))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(tdir), profiler_options=opts)
        t_window = time.perf_counter()
        with _span("bench.window"):
            runner = (run_open if cell.traffic["kind"] == "open"
                      else run_backlog)
            win = runner(engine, payloads, cell, seed, seconds)
        if trace:
            jax.profiler.stop_trace()
            counts = {k: v - counts0[k] for k, v in counters(engine).items()
                      if k in counts0}
        window_compiles = compiles.n - compiles0
        batches = engine.batches_seen - batches0
    mem = memory_peak(devices)
    _log(f"window compiles (or cache loads): {window_compiles}")
    engine = params = None
    gc.unfreeze()
    gc.collect()

    setup_s = t_window - t_start
    attempted = len(win.parts)
    failed = attempted - len(win.done)
    rows = win.rows_done
    e2e = {"rows_per_s": rows / win.t_end if win.t_end > 0 else 0.0,
           "setup_s": setup_s}
    lat = []
    if win.late_s:
        lat = [t - win.parts[r].arrival for r, (t, _) in win.done.items()]
        _log(f"generator late by median {1e3 * np.median(win.late_s):.3f} "
             f"ms, max {1e3 * max(win.late_s):.3f} ms; latency p50 "
             f"{1e3 * layers.nearest_rank(lat, 0.5)!r} ms, p90 "
             f"{1e3 * layers.nearest_rank(lat, 0.9)!r} ms")
    slots = valid_slots(win, payloads)
    _log(f"window: {attempted} requests, {rows} rows, {slots} valid "
         f"slots, {batches} batches, {win.calls} serve calls, "
         f"{1e3 * win.serve_s / max(batches, 1):.3f} ms in serve() per "
         f"batch, longest call {1e3 * win.longest_s:.3f} ms, "
         f"{win.t_end:.3f} s, "
         + ", ".join(f"{k} {v!r}" for k, v in e2e.items()))

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"metrics": {}, "device": device}
    if trace:
        xplane = trace_mod.find_xplane(tdir)
        if keep_trace is not None:
            keep_trace.parent.mkdir(parents=True, exist_ok=True)
            shutil.copy(xplane, keep_trace)
        tr = trace_mod.load(xplane)
        shutil.rmtree(tdir, ignore_errors=True)
        spans = tr.span("bench.window")
        if not spans:
            raise BenchError("the trace holds no bench.window span")
        lo, hi = spans[0]
        reading = Reading(cell, peak, rows, slots, batches, win.serve_s,
                          win.t_end, tr, lo, hi, counts, lat)
        for m in cell.per_layer:
            v = load_reader(m["name"])(reading)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        device["busy_s"] = trace_mod.busy_s(tr, lo, hi)
        device["window_s"] = (hi - lo) / 1e9
        mods = sorted(reading.modules().items(), key=lambda x: -x[1])
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in mods[:10]],
            "idle_gaps": [[n, s] for n, s in
                          trace_mod.idle_gaps(tr, lo, hi, SPANS)]}
    else:
        for m in cell.end_to_end:
            if m["name"] in e2e:
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}

    t_check = time.perf_counter()
    got = check(cell, seed, win, payloads)
    limit = cell.config["limits"]["score_gap"]
    gap = got["score_gap"]
    correct = bool(failed == 0 and gap <= limit)
    checks = {"score_gap": {"value": gap if math.isfinite(gap) else None,
                            "limit": limit},
              "failed": {"value": failed, "limit": 0}}
    _log(f"compared {got['rows']} rows of {len(sample(win, seed))} "
         f"requests in {time.perf_counter() - t_check:.3f} s")
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return {"correct": correct, "attempted": attempted, "failed": failed,
            **result, "window_compiles": window_compiles, "checks": checks}
