"""Pallas TPU kernel: fused staleness-decay gradient aggregation.

The PS-side hot loop of GBA (Alg. 2 lines 20/22): given the M-slot gradient
buffer ``(M, D)``, the slot tokens ``(M,)`` and the current global step,
compute ``sum_m f(token_m, k) * g_m / M`` — decay mask, weighting and
reduction in one VMEM pass instead of XLA's mask -> broadcast-mul -> reduce
chain (3x HBM traffic on the buffer).

TPU adaptation: the buffer is tiled along D into ``(M, BLOCK_D)`` VMEM
blocks (M is small — 8..100 — so a full buffer column always fits VMEM);
tokens ride in SMEM via ``PrefetchScalarGridSpec`` so the mask is computed
on the scalar core before the vector pass.

NOTE: the train path now prefers ``repro.kernels.gba_apply``, which fuses
this reduction WITH the Adagrad update so the aggregated gradient never
round-trips through HBM; this standalone kernel remains for tree-level
aggregation (``ops.gba_aggregate_tree``) and non-Adagrad optimizers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import runtime
from repro.kernels.launch_meta import (BlockMeta, LaunchMeta, block_specs,
                                       _round_up_static)

BLOCK_D = 2048


def aggregate_vmem_bytes(m: int, block_d: int = BLOCK_D,
                         itemsize: int = 4) -> int:
    """Per-grid-step VMEM residency: the (m, BLOCK_D) buffer block plus
    the (BLOCK_D,) output block, in the buffer dtype."""
    return (m + 1) * block_d * itemsize


def launch_meta(d: int, m: int, dtype=jnp.float32) -> LaunchMeta:
    """Static launch geometry for an (m, d)-buffer aggregate; the
    pallas_call builds its specs from this."""
    d_pad = _round_up_static(d, BLOCK_D)
    itemsize = jnp.dtype(dtype).itemsize
    return LaunchMeta(
        kernel="gba_aggregate",
        grid=(d_pad // BLOCK_D,),
        num_scalar_prefetch=3,
        inputs=(
            BlockMeta("grads", (m, d_pad), dtype, (m, BLOCK_D),
                      lambda i, *_: (0, i)),
        ),
        outputs=(
            BlockMeta("out", (d_pad,), dtype, (BLOCK_D,),
                      lambda i, *_: (i,)),
        ),
        declared_vmem_bytes=aggregate_vmem_bytes(m, BLOCK_D, itemsize),
        vmem_counted=("grads", "out"),
    )


def _kernel(tokens_ref, step_ref, iota_ref, grads_ref, out_ref):
    """grads_ref: (M, BLOCK_D) VMEM block; tokens/step/iota in SMEM, read
    as scalars (the scalar core cannot load SMEM vectors)."""
    m = grads_ref.shape[0]
    g = None
    for k in range(m):
        keep = (step_ref[0] - tokens_ref[k]) <= iota_ref[0]   # Eq. (1)
        w = keep.astype(jnp.float32) / jnp.float32(m)
        row = grads_ref[k, :].astype(jnp.float32) * w
        g = row if g is None else g + row
    out_ref[...] = g.astype(out_ref.dtype)


def gba_aggregate(grads: jax.Array, tokens: jax.Array, step: jax.Array,
                  *, iota: int, interpret: bool | None = None) -> jax.Array:
    """grads: (M, D) -> (D,) decayed mean.  ``interpret=None`` resolves
    through ``repro.kernels.runtime``."""
    return _gba_aggregate(grads, tokens, step, iota=iota,
                          interpret=runtime.resolve(interpret))


@functools.partial(jax.jit, static_argnames=("iota", "interpret"))
def _gba_aggregate(grads, tokens, step, *, iota: int, interpret: bool):
    m, d = grads.shape
    pad = (-d) % BLOCK_D
    if pad:
        grads = jnp.pad(grads, ((0, 0), (0, pad)))
    d_pad = d + pad
    meta = launch_meta(d, m, grads.dtype)

    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=meta.num_scalar_prefetch,
            grid=meta.grid,
            in_specs=block_specs(meta.inputs),
            out_specs=block_specs(meta.outputs)[0],
        ),
        out_shape=jax.ShapeDtypeStruct((d_pad,), grads.dtype),
        interpret=interpret,
    )(tokens.astype(jnp.int32),
      jnp.asarray(step, jnp.int32).reshape(1),
      jnp.full((1,), iota, jnp.int32),
      grads)
    return out[:d]
