"""Model families: what the harness needs to know of a configuration's
model, by the configuration's ``family`` key.

A family is a module ``bench/families/<family>.py`` that gives

- ``model_config(cfg)``: the program's ``ModelConfig`` of the
  configuration, which ``ClusterEngine`` serves;
- ``leaves(cfg)``: every parameter as ``(path, shape, scale)``, in the
  order ``weights.make`` draws them;
- ``make_rows(cfg, n, rng, traffic)``: ``n`` candidate rows ``(dense,
  indices)`` drawn with ``rng`` under the traffic file's parameters, with
  ``-1`` in every index slot that holds no row;
- ``dense_flops_per_row(cfg)`` (a dict of parts), ``dense_weight_bytes(cfg)``,
  ``dense_activation_bytes(cfg, rows)`` and ``bag_bytes(cfg, rows,
  valid_slots)`` (a dict of parts): the counts the per-layer metrics hold
  against the peaks.

Its plain reference is ``bench/references/<reference>.py``, named by the
configuration's ``reference`` key.  So a new family is a configuration
file, a family module and a reference module, all new files.
"""
from __future__ import annotations

import importlib
import pkgutil
from types import ModuleType
from typing import Dict, List

from bench.errors import BenchError


def known() -> List[str]:
    """The families on the search path (this package's ``__path__``)."""
    return sorted(m.name for m in pkgutil.iter_modules(__path__))


def family(cfg: Dict) -> ModuleType:
    """The family module of configuration ``cfg``."""
    name = cfg.get("family")
    if isinstance(name, str) and name.isidentifier():
        full = f"{__name__}.{name}"
        try:
            return importlib.import_module(full)
        except ModuleNotFoundError as e:
            if e.name != full:
                raise
    raise BenchError(f"configuration {cfg.get('name')!r} names unknown "
                     f"family {name!r}; known: {', '.join(known())}")
