"""Activation sharding constraints.

GSPMD resolves the sharding conflict at the embedding gather (tokens
batch-sharded over `data` vs table dim-sharded over `data`) by *replicating
the batch* — every downstream activation then loses data parallelism (seen
as full-batch f32 temps in the HLO and TB-scale memory terms).

The fix is the canonical one: pin the residual stream to the
megatron-style layout P(data_axes, None, None) at block boundaries.
``set_act_spec`` is called by launch.steps before tracing; models call
``constrain`` on (B, S, D) activations.  Outside a mesh context (smoke
tests) the spec is None and ``constrain`` is the identity.
"""
from __future__ import annotations

import jax

_ACT_SHARDING = None


def set_act_spec(sharding) -> None:
    """sharding: a NamedSharding for (B, S, D) activations, or None."""
    global _ACT_SHARDING
    _ACT_SHARDING = sharding


def constrain(x: jax.Array) -> jax.Array:
    if _ACT_SHARDING is None or x.ndim != 3:
        return x
    return jax.lax.with_sharding_constraint(x, _ACT_SHARDING)
