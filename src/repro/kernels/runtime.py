"""Interpret-mode resolution shared by every kernel wrapper.

Every kernel function takes ``interpret: bool | None = None`` and resolves
``None`` here: on a TPU the kernels compile through Mosaic, anywhere else
(the CPU test suite) they run in Pallas interpret mode.  Resolution order:

1. an explicit per-call ``interpret=`` override (never resolved here),
2. ``set_interpret(...)`` — programmatic override for launch scripts,
3. the ``REPRO_INTERPRET`` env var (``0``/``false``/``off`` compile,
   anything else interprets),
4. the platform: ``jax.default_backend() != "tpu"``.

Steps 2 and 3 can force interpret mode on a TPU; ``chip_smoke.py`` refuses
to run when they do.  The platform probe is deferred to first use so
importing kernel modules never initializes the JAX backend.
"""
from __future__ import annotations

import os

_TRUTHY_OFF = ("0", "false", "no", "off", "")

_INTERPRET: bool | None = None


def default_interpret() -> bool:
    """Environment/platform default, ignoring any set_interpret override."""
    env = os.environ.get("REPRO_INTERPRET")
    if env is not None:
        return env.strip().lower() not in _TRUTHY_OFF
    import jax
    return jax.default_backend() != "tpu"


def interpret_mode() -> bool:
    """The process-wide interpret default (cached after first resolution)."""
    global _INTERPRET
    if _INTERPRET is None:
        _INTERPRET = default_interpret()
    return _INTERPRET


def set_interpret(value: bool | None) -> None:
    """Force interpret mode on/off; ``None`` re-enables auto-resolution."""
    global _INTERPRET
    _INTERPRET = value


def resolve(override: bool | None) -> bool:
    """Per-call resolution: explicit override wins, else the process-wide mode."""
    return interpret_mode() if override is None else override
