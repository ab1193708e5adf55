"""Pallas TPU kernel: blocked decode attention (beyond-paper).

One new token attends to a long KV cache (decode_32k / long_500k shapes).
The naive XLA lowering materializes the full (H, L) score row in f32 and
reads it three times (max, exp-sum, weighted sum).  This kernel streams the
cache in (BLOCK_L) chunks with an online-softmax accumulator held in VMEM
scratch — one HBM pass over K and V, which is the roofline for decode.

Grid: (B, L/BLOCK_L); the L dimension is sequential ("arbitrary") so the
scratch accumulators carry across cache blocks; batch is parallel.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import runtime
from repro.kernels.launch_meta import (BlockMeta, LaunchMeta, ScratchMeta,
                                       block_specs, scratch_shapes)

BLOCK_L = 512


def decode_vmem_bytes(kv: int, g: int, hd: int, l: int,
                      itemsize: int = 4) -> int:
    """Per-grid-step VMEM residency: q + output blocks, two (blk, KV, hd)
    cache blocks, and the f32 online-softmax accumulators."""
    blk = min(BLOCK_L, l)
    return ((2 * kv * g * hd + 2 * blk * kv * hd) * itemsize
            + (2 * kv * g + kv * g * hd) * 4)


def launch_meta(b: int, l: int, kv: int, g: int, hd: int,
                dtype=jnp.float32) -> LaunchMeta:
    """Static launch geometry for a (B, KV, G, hd) x (B, L, KV, hd)
    decode; the pallas_call builds its specs and scratch from this."""
    blk = min(BLOCK_L, l)
    return LaunchMeta(
        kernel="flash_decode",
        grid=(b, l // blk),
        num_scalar_prefetch=1,
        inputs=(
            BlockMeta("q", (b, kv, g, hd), dtype, (1, kv, g, hd),
                      lambda bi, j, *_: (bi, 0, 0, 0)),
            BlockMeta("k", (b, l, kv, hd), dtype, (1, blk, kv, hd),
                      lambda bi, j, *_: (bi, j, 0, 0)),
            BlockMeta("v", (b, l, kv, hd), dtype, (1, blk, kv, hd),
                      lambda bi, j, *_: (bi, j, 0, 0)),
        ),
        outputs=(
            BlockMeta("o", (b, kv, g, hd), dtype, (1, kv, g, hd),
                      lambda bi, j, *_: (bi, 0, 0, 0)),
        ),
        scratch=(
            ScratchMeta("m_scratch", (kv, g), jnp.float32),
            ScratchMeta("l_scratch", (kv, g), jnp.float32),
            ScratchMeta("acc_scratch", (kv, g, hd), jnp.float32),
        ),
        declared_vmem_bytes=decode_vmem_bytes(
            kv, g, hd, l, jnp.dtype(dtype).itemsize),
        vmem_counted=("q", "k", "v", "o", "m_scratch", "l_scratch",
                      "acc_scratch"),
    )


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, blk: int):
    j = pl.program_id(1)
    nblk = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)            # (KV, G, hd)
    k = k_ref[0].astype(jnp.float32)            # (BLK, KV, hd)
    v = v_ref[0].astype(jnp.float32)
    hd = q.shape[-1]
    scores = jnp.einsum("ngh,lnh->ngl", q, k) / math.sqrt(hd)
    # causal/validity mask: absolute cache index <= pos
    idx = j * blk + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 2)
    scores = jnp.where(idx <= pos_ref[0], scores, -1e30)

    m_prev = m_ref[...]                          # (KV, G)
    m_new = jnp.maximum(m_prev, scores.max(axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new[..., None])       # (KV, G, BLK)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    acc_ref[...] = (acc_ref[...] * alpha[..., None]
                    + jnp.einsum("ngl,lnh->ngh", p, v))
    m_ref[...] = m_new

    @pl.when(j == nblk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / l_ref[...][..., None]).astype(o_ref.dtype)


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array, pos: jax.Array,
                 *, interpret: bool | None = None) -> jax.Array:
    """q: (B, KV, G, hd) one-token queries grouped by kv head;
    k/v: (B, L, KV, hd) cache; pos: scalar int32 (last valid index).
    Returns (B, KV, G, hd)."""
    return _flash_decode(q, k, v, pos, interpret=runtime.resolve(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _flash_decode(q, k, v, pos, *, interpret: bool) -> jax.Array:
    B, KV, G, hd = q.shape
    L = k.shape[1]
    blk = min(BLOCK_L, L)
    assert L % blk == 0
    meta = launch_meta(B, L, KV, G, hd, q.dtype)
    out = pl.pallas_call(
        functools.partial(_kernel, blk=blk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=meta.num_scalar_prefetch,
            grid=meta.grid,
            in_specs=block_specs(meta.inputs),
            out_specs=block_specs(meta.outputs)[0],
            scratch_shapes=scratch_shapes(meta.scratch),
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(pos, jnp.int32).reshape(1), q, k, v)
    return out
