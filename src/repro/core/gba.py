"""GBA aggregation — the paper's core op, as jittable JAX functions.

Two entry points:

* :func:`aggregate_dense` — Algorithm 2 lines 20/22: decay each of the M
  buffered gradients by the token-control rule, weighted-sum, divide by
  ``N_a = M``.  Used for every dense parameter and, stacked per-leaf, for
  whole LM parameter pytrees.

* :func:`aggregate_embedding` — Algorithm 2 lines 21/23: per-ID treatment of
  the sparse module.  Each buffered sparse gradient arrives as (ids, rows);
  a row is decayed against the global step *its ID* last saw (the tagged
  ``last_update``), and the aggregate is divided by the number of buffer
  slots that actually touched the ID — not by M.

Both are pure functions usable inside pjit/shard_map; the Pallas kernel in
``repro.kernels.gba_aggregate`` is a drop-in replacement for the inner
weighted reduction of :func:`aggregate_dense`.

Flat-buffer layout (the PS hot path)
------------------------------------
``buffer_push_and_maybe_apply`` keeps the buffer as a pytree mirroring the
gradients — one XLA op chain per leaf on every push AND every apply.  The
fused path instead ravels all dense leaves into a single ``(M, N_total)``
f32 buffer using :class:`FlatLayout`: leaves are laid out back-to-back in
treedef order, each occupying ``[offsets[j], offsets[j] + sizes[j])`` of
the flat axis.  A push is then one ``dynamic_update_index_in_dim`` and an
apply is ONE launch of the fused ``repro.kernels.gba_apply`` kernel
(token-decay aggregation + Adagrad in a single VMEM pass), instead of a
per-leaf aggregate -> HBM -> per-leaf optimizer chain.  See
:func:`init_flat_buffer` / :func:`flat_buffer_push_and_maybe_apply`.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.staleness import DECAY_FNS, threshold_decay

Params = Any


def decay_weights(tokens: jax.Array, global_step: jax.Array, iota: int,
                  strategy: str = "threshold") -> jax.Array:
    """(M,) aggregation weights from the token-control rule."""
    return DECAY_FNS[strategy](tokens, global_step, iota)


def aggregate_dense(grads_stacked: Params, tokens: jax.Array,
                    global_step: jax.Array, iota: int,
                    strategy: str = "threshold") -> Params:
    """grads_stacked: pytree with leading M axis -> decayed mean over M.

    Follows Alg. 2 line 22: weighted sum divided by N_a (= M), so dropped
    slots shrink the effective gradient rather than re-normalizing — the
    paper's choice, which keeps the update scale consistent with a full
    buffer."""
    w = decay_weights(tokens, global_step, iota, strategy)
    m = w.shape[0]

    def agg(g):
        wf = w.reshape((m,) + (1,) * (g.ndim - 1)).astype(jnp.float32)
        return (jnp.sum(g.astype(jnp.float32) * wf, axis=0) / m).astype(
            g.dtype)

    return jax.tree.map(agg, grads_stacked)


def aggregate_embedding(ids_stacked: jax.Array, rows_stacked: jax.Array,
                        tokens: jax.Array, last_update: jax.Array,
                        global_step: jax.Array, iota: int, capacity: int,
                        valid: jax.Array | None = None
                        ) -> tuple[jax.Array, jax.Array]:
    """Per-ID sparse aggregation (Alg. 2 lines 21/23).

    ids_stacked:  (M, n) int32 hashed IDs per buffer slot
    rows_stacked: (M, n, D) gradient rows aligned with ids
    tokens:       (M,) slot tokens
    last_update:  (capacity,) int32 global step each ID last saw
    valid:        optional (M, n) bool — explicit padding mask; False
                  slots are excluded outright

    A slot's row for an ID is kept iff the ID is *not* severely stale w.r.t.
    that slot's token: either the ID has not been updated since the token
    was issued (data unchanged -> gradient still valid, Insight 2), or the
    staleness k - token is within iota.  Kept rows are summed and divided by
    the number of slots that touched the ID.

    Padded batches: IDs outside ``[0, capacity)`` — the streamed kernels'
    sentinel convention (``repro.kernels.embedding_bag`` maps batch
    padding to an out-of-range sentinel) — are treated as padding and
    contribute to NEITHER the dense aggregate NOR the per-ID contributor
    counts (Alg. 2 line 23's divisor counts real contributors only).
    Without the mask a padded slot would inflate ``counts`` for whatever
    row its sentinel aliased (negative IDs wrap in XLA scatters) and
    scatter ghost gradient rows into the aggregate.

    Returns (dense_grad (capacity, D), counts (capacity,)).
    """
    M, n = ids_stacked.shape
    D = rows_stacked.shape[-1]
    # padding mask: the kernels' sentinel-ID convention, optionally ANDed
    # with an explicit caller mask
    in_range = (ids_stacked >= 0) & (ids_stacked < capacity)     # (M, n)
    if valid is not None:
        in_range = in_range & valid
    safe_ids = jnp.where(in_range, ids_stacked, 0)
    # slot-level hard threshold (same Eq. (1) clock)...
    slot_ok = (global_step - tokens) <= iota                     # (M,)
    # ...relaxed per-ID: if the ID was never updated after the token was
    # issued, its gradient is exact regardless of slot staleness.
    id_last = last_update[safe_ids]                              # (M, n)
    id_fresh = id_last <= tokens[:, None]
    keep = (slot_ok[:, None] | id_fresh) & in_range              # (M, n)

    flat_ids = safe_ids.reshape(-1)
    flat_keep = keep.reshape(-1).astype(jnp.float32)
    flat_rows = rows_stacked.reshape(-1, D).astype(jnp.float32)
    flat_rows = flat_rows * flat_keep[:, None]

    dense = jnp.zeros((capacity, D), jnp.float32).at[flat_ids].add(flat_rows)
    counts = jnp.zeros((capacity,), jnp.float32).at[flat_ids].add(flat_keep)
    dense = dense / jnp.maximum(counts, 1.0)[:, None]
    return dense, counts


# ---------------------------------------------------------------------------
# GBA as a first-class train-step transform
# ---------------------------------------------------------------------------

def init_buffer(params: Params, buffer_size: int) -> dict:
    """M-slot gradient buffer living alongside the optimizer state.  Each
    leaf gets a leading M axis; sharded exactly like the gradient."""
    return {
        "grads": jax.tree.map(
            lambda p: jnp.zeros((buffer_size,) + p.shape, p.dtype), params),
        "tokens": jnp.zeros((buffer_size,), jnp.int32),
        "fill": jnp.zeros((), jnp.int32),
        "step": jnp.zeros((), jnp.int32),
    }


def buffer_push_and_maybe_apply(
        buffer: dict, grads: Params, token: jax.Array, iota: int,
        apply_fn: Callable[[Params], tuple], noop_fn: Callable[[], tuple],
        strategy: str = "threshold"):
    """Push one gradient into the buffer; when full, decay-aggregate and call
    ``apply_fn(agg_grads)``, else ``noop_fn()``.  Pure function of its
    inputs; lowers to a single ``lax.cond`` — this is the shape the sharded
    train step uses so that GBA is part of the compiled program."""
    m = buffer["tokens"].shape[0]
    slot = buffer["fill"] % m
    new_grads = jax.tree.map(
        lambda b, g: jax.lax.dynamic_update_index_in_dim(
            b, g.astype(b.dtype), slot, 0),
        buffer["grads"], grads)
    new_tokens = jax.lax.dynamic_update_index_in_dim(
        buffer["tokens"], token.astype(jnp.int32), slot, 0)
    fill = buffer["fill"] + 1
    is_full = (fill % m) == 0

    def do_apply(operands):
        bgrads, btokens, step = operands
        agg = aggregate_dense(bgrads, btokens, step, iota, strategy)
        return apply_fn(agg)

    def do_noop(operands):
        return noop_fn()

    out = jax.lax.cond(is_full, do_apply, do_noop,
                       (new_grads, new_tokens, buffer["step"]))
    new_buffer = {
        "grads": new_grads,
        "tokens": new_tokens,
        "fill": fill,
        "step": buffer["step"] + is_full.astype(jnp.int32),
    }
    return out, new_buffer


# ---------------------------------------------------------------------------
# flat buffer: one (M, N_total) array + offsets table -> one kernel launch
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class FlatLayout:
    """Ravel/unravel a dense parameter pytree to one flat f32 vector.

    Leaves are concatenated in ``jax.tree`` (treedef) order; leaf ``j``
    lives at ``flat[offsets[j] : offsets[j] + sizes[j]]``.  The layout is a
    host-side object (hashable tuples only) so it can be closed over by
    jitted train steps.
    """

    treedef: Any
    shapes: tuple[tuple[int, ...], ...]
    dtypes: tuple[Any, ...]
    sizes: tuple[int, ...]
    offsets: tuple[int, ...]
    total: int

    @classmethod
    def from_params(cls, params: Params) -> "FlatLayout":
        leaves, treedef = jax.tree.flatten(params)
        shapes = tuple(tuple(l.shape) for l in leaves)
        dtypes = tuple(l.dtype for l in leaves)
        sizes = tuple(math.prod(s) for s in shapes)
        offsets = []
        off = 0
        for s in sizes:
            offsets.append(off)
            off += s
        return cls(treedef, shapes, dtypes, sizes, tuple(offsets), off)

    def ravel(self, tree: Params) -> jax.Array:
        leaves = jax.tree.leaves(tree)
        return jnp.concatenate(
            [l.reshape(-1).astype(jnp.float32) for l in leaves])

    def unravel(self, flat: jax.Array) -> Params:
        leaves = [
            flat[o:o + n].reshape(s).astype(dt)
            for o, n, s, dt in zip(self.offsets, self.sizes, self.shapes,
                                   self.dtypes)
        ]
        return jax.tree.unflatten(self.treedef, leaves)


def init_flat_buffer(params: Params, buffer_size: int
                     ) -> tuple[FlatLayout, dict]:
    """Flat M-slot gradient buffer: one (M, N_total) array instead of a
    leading-M pytree.  Returns (layout, buffer)."""
    layout = FlatLayout.from_params(params)
    return layout, {
        "grads": jnp.zeros((buffer_size, layout.total), jnp.float32),
        "tokens": jnp.zeros((buffer_size,), jnp.int32),
        "fill": jnp.zeros((), jnp.int32),
        "step": jnp.zeros((), jnp.int32),
    }


def flat_buffer_push(buffer: dict, flat_grad: jax.Array, token: jax.Array
                     ) -> tuple[dict, jax.Array]:
    """Push one raveled gradient into the flat buffer.  Returns
    ``(new_buffer, is_full)``; ``new_buffer["step"]`` is already advanced
    when the push filled the buffer, but ``new_buffer["tokens"]`` /
    ``["grads"]`` still hold the slots for the apply that must follow
    (the single source of slot/fill/step arithmetic for the fused path).
    """
    m = buffer["tokens"].shape[0]
    slot = buffer["fill"] % m
    new_grads = jax.lax.dynamic_update_index_in_dim(
        buffer["grads"], flat_grad.astype(jnp.float32), slot, 0)
    new_tokens = jax.lax.dynamic_update_index_in_dim(
        buffer["tokens"], token.astype(jnp.int32), slot, 0)
    fill = buffer["fill"] + 1
    is_full = (fill % m) == 0
    new_buffer = {
        "grads": new_grads,
        "tokens": new_tokens,
        "fill": fill,
        "step": buffer["step"] + is_full.astype(jnp.int32),
    }
    return new_buffer, is_full


def flat_buffer_push_and_maybe_apply(
        buffer: dict, flat_grad: jax.Array, token: jax.Array,
        param_flat: jax.Array, accum_flat: jax.Array, lr, *, iota: int,
        eps: float = 1e-10, interpret: bool | None = None):
    """Fused-path counterpart of :func:`buffer_push_and_maybe_apply`.

    Pushes one raveled gradient; when the buffer fills, runs the fused
    ``gba_apply`` Pallas kernel (decay-aggregate + Adagrad, one launch for
    the whole dense module).  Returns
    ``(new_param_flat, new_accum_flat, applied, new_buffer)`` — on non-full
    pushes params/accum pass through unchanged.

    Callers that keep params as a pytree (``launch.steps``'s fused train
    step ravels/unravels inside the apply branch only) use
    :func:`flat_buffer_push` directly and wrap their own ``lax.cond``.
    """
    from repro.kernels import ops

    new_buffer, is_full = flat_buffer_push(buffer, flat_grad, token)

    def do_apply(operands):
        p, a, grads, tokens, step = operands
        return ops.gba_apply_flat(p, a, grads, tokens, step, lr,
                                  iota=iota, eps=eps, interpret=interpret)

    def do_noop(operands):
        p, a, *_ = operands
        return p, a

    new_param, new_accum = jax.lax.cond(
        is_full, do_apply, do_noop,
        (param_flat, accum_flat, new_buffer["grads"], new_buffer["tokens"],
         buffer["step"]))
    return new_param, new_accum, is_full, new_buffer
