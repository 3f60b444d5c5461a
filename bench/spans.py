"""Where the host's time inside serve() goes, step by step, read from the
served path's own spans in a kept profiler trace.

The program (``repro.serving``) marks each host step of a batch with a
``jax.profiler.TraceAnnotation`` named ``repro.<step>``; the leaf spans of
one batch never overlap.  This module gives, per batch, the device-idle
milliseconds inside each group of leaves (``layers.GROUPS``), on the
definition of the harness's ``host_ms_per_batch``: span time less the
device-busy time inside it, and what no leaf holds.  It also names each
long idle gap by the span that holds most of it.

    python3 bench/run.py --workload rm1.backlog --seed 7 --seconds 51 \
        --trace 1 --keep-trace out/rm1.backlog.xplane.pb
    python3 bench/spans.py out/rm1.backlog.xplane.pb

prints one JSON object per trace.  The result line of a ``--trace 1`` run
carries the groups' numbers as per-layer metrics (``*_idle_ms.*``), read
by ``layers.span_idle_ms`` over the window; this split adds the remainder
and the named gaps.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path
from typing import Dict, List, Optional, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace as tr  # noqa: E402
from bench.layers import GROUPS, covered, idle_s, within  # noqa: E402

LEAF_SPANS = tuple(n for g in GROUPS.values() for n in g)

# spans that enclose the leaves, innermost first, then the harness's
ENCLOSING = (("repro.batch", "repro.serve"),
             ("bench.serve", "bench.wait", "bench.assemble"))

Span = Tuple[str, float, float, Dict]


def program_spans(profile) -> List[Span]:
    """Every ``repro.*`` event of the host plane as (name, start, end,
    metadata), by start."""
    return sorted(((ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
                   for ev in tr.host_events(profile, (tr.PROGRAM_PREFIX,))),
                  key=lambda x: x[1])


# the harness's reduction keeps the program's spans
load = tr.load


def named_gaps(t: tr.Trace, lo: float, hi: float, top: int = 10
               ) -> List[Tuple[str, float]]:
    """The ``top`` longest gaps with no operation on device 0, each named
    by the span that holds most of it to itself: a leaf, else the batch or
    call that encloses it, else the harness's span around the call, of
    each tier only the part no earlier tier covers.  ``"other"`` where no
    span touches the gap."""
    if not t.busy:
        return []
    tiers = [{n: tr.merge(t.span(n)) for n in tier}
             for tier in (LEAF_SPANS, *ENCLOSING)]
    out = []
    for s, e in tr.gaps(t.busy[0], lo, hi):
        name, most, before = "other", 0.0, []
        for named in tiers:
            free = tr.gaps(tr.merge(before), s, e)
            for n, ivs in named.items():
                own = sum(covered(ivs, a, b) for a, b in free)
                if own > most:
                    name, most = n, own
            before += [iv for ivs in named.values()
                       for iv in within(ivs, s, e)]
        out.append((name, (e - s) / 1e9))
    out.sort(key=lambda x: -x[1])
    return out[:top]


def split(t: tr.Trace, batches: Optional[int] = None) -> Dict:
    """Per batch of the traced window, the device-idle milliseconds inside
    ``bench.serve`` (the harness's ``host_ms_per_batch``) and inside each
    group of leaves, the part of the former no leaf holds, the window's
    device time per XLA module, and its longest idle gaps by name.
    ``batches`` defaults to the ``repro.batch`` spans in the window."""
    (lo, hi), = t.span("bench.window")
    if batches is None:
        batches = sum(1 for s, _ in t.span("repro.batch") if lo <= s < hi)
    if batches <= 0:
        raise ValueError("the window holds no batch")
    out: Dict = {"batches": batches,
                 "host_ms_per_batch": 1e3 * idle_s(t, t.span("bench.serve"))
                 / batches}
    for metric, names in GROUPS.items():
        out[metric] = 1e3 * idle_s(
            t, [iv for n in names for iv in t.span(n)]) / batches
    out["remainder_ms"] = out["host_ms_per_batch"] - sum(
        out[m] for m in GROUPS)
    mods = sorted(tr.module_seconds(t, lo, hi).items(), key=lambda x: -x[1])
    out["device_ops"] = [[n, s] for n, s in mods[:10]]
    out["idle_gaps"] = [[n, s] for n, s in named_gaps(t, lo, hi)]
    return out


def main(argv=None) -> int:
    paths = sys.argv[1:] if argv is None else argv
    if not paths:
        print(__doc__, file=sys.stderr)
        return 2
    for p in paths:
        print(json.dumps({"trace": str(p), **split(load(Path(p)))}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
