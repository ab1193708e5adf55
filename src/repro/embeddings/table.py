"""Hashing-trick embedding tables with per-ID update-step tracking.

This is the JAX stand-in for DeepRec's expandable HashTables (DESIGN.md §2):
IDs are hashed into a fixed-capacity table; each row carries the global step
of its last update (``last_update``), which implements Algorithm 2's per-ID
staleness decay — the embedding gradient of an ID is decayed against the
step *that ID* last saw, not the dense-parameter step.

``pooled_lookup`` is the kernel-backed sparse module: a differentiable
sum-pooled gather whose forward streams (BLOCK_V, BLOCK_D) table tiles out
of HBM and whose backward streams the sorted gradient rows — VMEM stays
O(block) at any capacity (``repro.kernels.embedding_bag``).  The capacity
knobs travel as a :class:`StreamConfig` so trainers and launch scripts can
size the blocks for their vocabulary.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import embedding_bag, ops

Params = dict[str, Any]

# Knuth multiplicative hashing: spreads raw categorical IDs over the table.
_HASH_MULT = jnp.uint32(2654435761)


class EmbeddingTable(NamedTuple):
    table: jax.Array        # (capacity, dim)
    last_update: jax.Array  # (capacity,) int32 — global step of last update


def init_table(key, capacity: int, dim: int, scale: float = 0.01
               ) -> EmbeddingTable:
    return EmbeddingTable(
        table=jax.random.normal(key, (capacity, dim), jnp.float32) * scale,
        last_update=jnp.zeros((capacity,), jnp.int32),
    )


def hash_ids(raw_ids: jax.Array, capacity: int) -> jax.Array:
    h = (raw_ids.astype(jnp.uint32) * _HASH_MULT) >> jnp.uint32(8)
    return (h % jnp.uint32(capacity)).astype(jnp.int32)


def lookup(tbl: EmbeddingTable, hashed_ids: jax.Array) -> jax.Array:
    """hashed_ids: (...,) int32 -> (..., dim)."""
    return tbl.table[hashed_ids]


class StreamConfig(NamedTuple):
    """Capacity knobs for the DMA-streamed embedding kernels.

    ``None`` fields fall back to the kernel-module defaults (BLOCK_V /
    BLOCK_D / CHUNK_E).  Hashable on purpose: it rides through
    ``jax.custom_vjp`` as a nondiff argument and through jit static args.
    """
    block_v: int | None = None   # vocab rows per streamed table tile
    block_d: int | None = None   # embedding columns per output tile
    chunk_e: int | None = None   # sorted entries per pipeline step
    interpret: bool | None = None


class _BagMeta(NamedTuple):
    """Static (hashable) side-channel for the custom VJP: the backward
    kernel needs the table's capacity/dtype, which residuals can't carry."""
    stream: StreamConfig
    capacity: int
    dtype: str


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _pooled_bag(table: jax.Array, hashed_ids: jax.Array,
                meta: _BagMeta) -> jax.Array:
    s = meta.stream
    return ops.pooled_lookup(hashed_ids, table, block_v=s.block_v,
                             block_d=s.block_d, chunk_e=s.chunk_e,
                             interpret=s.interpret)


def _pooled_bag_fwd(table, hashed_ids, meta):
    return _pooled_bag(table, hashed_ids, meta), hashed_ids


def _pooled_bag_bwd(meta, hashed_ids, g):
    # VJP of sum-pool = unnormalized scatter-add of g rows; the per-ID
    # counts the kernel co-produces belong to Alg. 2's aggregation rule,
    # not to autodiff, and are dropped here
    s = meta.stream
    gtable, _ = ops.pooled_lookup_grad(
        hashed_ids, g.astype(jnp.float32), meta.capacity, block_v=s.block_v,
        block_d=s.block_d, chunk_e=s.chunk_e, interpret=s.interpret)
    return gtable.astype(meta.dtype), jnp.zeros(hashed_ids.shape,
                                                jax.dtypes.float0)


_pooled_bag.defvjp(_pooled_bag_fwd, _pooled_bag_bwd)


def pooled_lookup(tbl: EmbeddingTable, hashed_ids: jax.Array, *,
                  stream: StreamConfig | None = None) -> jax.Array:
    """Differentiable sum-pooled lookup: (B, F) int32 -> (B, dim).

    Forward and backward are the streamed Pallas kernels — the (capacity,
    dim) table never materializes a VMEM-resident block, so this is the
    production-vocabulary path (10^6+ rows)."""
    meta = _BagMeta(stream or StreamConfig(), tbl.table.shape[0],
                    str(tbl.table.dtype))
    return _pooled_bag(tbl.table, hashed_ids, meta)


def presence_counts(hashed_ids: jax.Array, capacity: int, *,
                    stream: StreamConfig | None = None) -> jax.Array:
    """Per-ID occurrence counts of a batch of hashed IDs: (...,) int32 ->
    (capacity,) float32, via the streamed sorted-scatter kernel's counts
    output — O(block) VMEM at any capacity, unlike an XLA one-hot
    scatter which materializes the (capacity,)-wide one-hot adds."""
    s = stream or StreamConfig()
    ids2d = hashed_ids.reshape(1, -1)
    zero_rows = jnp.zeros((1, 1), jnp.float32)
    _, counts = ops.pooled_lookup_grad(
        ids2d, zero_rows, capacity, block_v=s.block_v, block_d=s.block_d,
        chunk_e=s.chunk_e, interpret=s.interpret)
    return counts


def scatter_rows(ids: jax.Array, rows: jax.Array, capacity: int,
                 counts: jax.Array | None = None, *,
                 stream: StreamConfig | None = None):
    """Each id's sum of the rows sent to it: ids (...) int32, rows
    ``ids.shape + (D,)`` -> (capacity, D), through the streamed
    sorted-scatter kernel: one sort, O(block) VMEM at any capacity, work
    that grows with the ids and not with any per-row copy of the
    table.  With ``counts`` (shaped like ``ids``, one value a (leading
    index, id) pair), also each id's sum of ``counts`` over its distinct
    pairs: ``(table, per_id)``."""
    s = stream or StreamConfig()
    return embedding_bag.scatter_rows(
        ids, rows, capacity, counts, block_v=s.block_v, chunk_e=s.chunk_e,
        interpret=s.interpret)


def sparse_grads_to_dense(ids: jax.Array, rows: jax.Array, capacity: int
                          ) -> tuple[jax.Array, jax.Array]:
    """Scatter (ids (N,), rows (N,D)) into a dense (capacity, D) grad and a
    per-row occurrence count (capacity,)."""
    ids = ids.reshape(-1)
    rows = rows.reshape(ids.shape[0], -1)
    dense = jnp.zeros((capacity, rows.shape[-1]), rows.dtype)
    dense = dense.at[ids].add(rows)
    counts = jnp.zeros((capacity,), jnp.float32).at[ids].add(1.0)
    return dense, counts


def apply_sparse_grads(tbl: EmbeddingTable, dense_grad: jax.Array,
                       counts: jax.Array, lr: float, global_step: jax.Array
                       ) -> EmbeddingTable:
    """SGD apply of an aggregated sparse gradient; rows with counts>0 get
    their ``last_update`` stamped to ``global_step`` (Alg. 2 line 19)."""
    touched = counts > 0
    new_table = tbl.table - lr * dense_grad
    new_table = jnp.where(touched[:, None], new_table, tbl.table)
    new_last = jnp.where(touched, global_step, tbl.last_update)
    return EmbeddingTable(new_table, new_last.astype(jnp.int32))
