"""Required operations and bytes of one optimizer step, from shapes alone.

"Required" means what the forward and backward passes of the mathematics
need: recomputed operations (rematerialised logits, the score matrix the
flash backward rebuilds) never count, and neither does the masked half of
causal attention.

A configuration names the module that reckons its work under ``"flops"``, as
it names its ``system`` and ``reference``. Such a module defines
``required_flops_per_step(cfg, traffic) -> float`` and may define
``flash_required_per_step(cfg, traffic) -> {"flops", "bytes"}``. Nothing here
knows a family: a new one brings its module (``benchmarks/flops/lm.py`` and
``vgg.py`` are the two there are).
"""

from __future__ import annotations

import importlib


def _module(cfg: dict):
    return importlib.import_module(cfg["flops"])


def required_flops_per_step(cfg: dict, traffic: dict) -> float:
    return float(_module(cfg).required_flops_per_step(cfg, traffic))


def flash_required_per_step(cfg: dict, traffic: dict) -> dict | None:
    """None where the configuration's module reckons no flash call."""
    reckon = getattr(_module(cfg), "flash_required_per_step", None)
    return reckon(cfg, traffic) if reckon is not None else None
