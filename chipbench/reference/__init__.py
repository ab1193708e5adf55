"""Plain references, one module per model, found by the configuration's
``model`` key.  A model module gives ``init``, ``logit``, ``ids``,
``ids_per_example``, ``forward_flops`` and ``SPARSE`` (the names of the
sparse module's leaves)."""
from __future__ import annotations

import importlib


def model_module(cfg: dict):
    return importlib.import_module(f"chipbench.reference.{cfg['model']}")
