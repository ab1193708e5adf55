"""Plain references, one module per model, found by the configuration's
``model`` key.  A model module gives ``init``, ``logit``, ``ids``,
``ids_per_example``, ``forward_flops`` and ``SPARSE`` (the names of the
sparse module's leaves).  Optional: ``loss(p, cfg, batch)``, the mean
training loss where it is more than the binary cross-entropy of the logit
(``train.Reference`` differentiates it); ``MATMULS_VIA_DOT = True`` where
every matrix product goes through ``precision.dot``, which a
configuration stating ``high`` or ``highest`` needs for its control."""
from __future__ import annotations

import importlib


def model_module(cfg: dict):
    return importlib.import_module(f"chipbench.reference.{cfg['model']}")
