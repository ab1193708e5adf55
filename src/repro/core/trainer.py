"""Schedule-replay trainer: PS-semantics training in JAX.

``repro.sim.cluster.simulate`` turns a cluster scenario + training mode into
a :class:`Schedule`; this module replays it with *real* gradients: the
gradient of every slot is computed against the parameter version of its
``dispatch_step`` (a ring of recent versions), then aggregated with the
mode's rule — GBA's token decay + per-ID embedding treatment, BSP's plain
mean, Hop-BW's drop-slowest, async's immediate apply.

Each global step is ONE jitted call: the M slot batches are stacked, the
per-slot gradients come from a single ``vmap`` over stacked parameter
versions, and the whole aggregate — token-decay weighting of the dense
module, per-ID mask/count accumulation for the sparse module, contributor
normalization, optimizer update and ``last_update`` stamping — happens
inside the compiled step.  A slot's loss is differentiated by the dense
leaves and by the table rows the slot gathered, never by the table, so the
sparse module's gradient is one weighted scatter of the step's M x n_ids
rows into one table-sized array per leaf: work that grows with the ids,
not with M copies of the table.  The previous implementation dispatched M
sequential ``value_and_grad`` calls per step and accumulated masks in
Python; the batched step removes that host round-trip from the PS hot loop.

The stacked versions come from a second compiled program,
``_stack_versions``: the M slots' source trees (a version shared by
several slots appears once per slot) go in, the ``(M, ...)`` tree comes
out, one dispatch a step instead of an eager ``jnp.stack`` per leaf (M+1
programs each).  It is a copy, bit-identical to the eager stack; the step
takes its result as the ``src_params`` argument.  A step whose slots all
share one version (``shared_src``) stacks nothing: the step broadcasts
that version.

This gives the accuracy experiments (paper Figs. 2/6/7/8) exact parameter-
server staleness semantics while remaining deterministic and laptop-fast.
"""
from __future__ import annotations

import collections
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.configs.recsys import RecsysConfig
from repro.data.clickstream import ClickStream
from repro.embeddings.table import (StreamConfig, scatter_rows,
                                    sparse_grads_to_dense)
from repro.metrics import StreamingAUC
from repro.models import recsys as R
from repro.optim import Optimizer
from repro.sim.cluster import Schedule

Params = Any

EMBED_KEYS = R.SPARSE_KEYS   # the sparse module


@dataclass
class ReplayStats:
    applied_steps: int = 0
    kept_slots: int = 0
    dropped_slots: int = 0
    history_clamps: int = 0
    embed_rows_rescued: int = 0     # per-ID relaxation kept a stale slot's row
    scattered_rows: int = 0         # (slot, id) rows the row scatter summed
    stacked_steps: int = 0          # steps that stacked M parameter versions
    # step variant (gba, m, shared_src) built -> the step (applied_steps)
    # whose call built it
    step_builds: dict[tuple[bool, int, bool], int] = field(
        default_factory=dict)
    losses: list[float] = field(default_factory=list)


class VersionRing:
    """Last-H parameter versions for delayed-gradient computation."""

    def __init__(self, history: int):
        self._h = history
        self._ring: collections.OrderedDict[int, Params] = \
            collections.OrderedDict()

    def put(self, version: int, params: Params):
        self._ring[version] = params
        while len(self._ring) > self._h:
            self._ring.popitem(last=False)

    def get(self, version: int) -> tuple[Params, bool]:
        if version in self._ring:
            return self._ring[version], False
        oldest = next(iter(self._ring))
        return self._ring[oldest], True


# jit keys this on M and the leaves' shapes, so it compiles once per M.
# Nothing is donated: the ring keeps its versions.
_stack_versions = jax.jit(
    lambda srcs: jax.tree.map(lambda *xs: jnp.stack(xs), *srcs))


@dataclass
class GBATrainer:
    cfg: RecsysConfig
    optimizer: Optimizer
    iota: int = 4
    per_id_embedding_decay: bool = True   # Alg. 2 lines 21/23
    history: int = 64
    # production-capacity knob: when set, the row scatter of the sparse
    # gradient and each id's contributor count run through the streamed
    # sorted-scatter kernel (O(block) VMEM at any hash_capacity) instead
    # of XLA scatters
    embed_stream: StreamConfig | None = None

    def __post_init__(self):
        # a slot's (loss, gradients); an attribute, so that a test can wrap
        # it before a step is built
        self._loss_grad_fn = self._slot_loss_grads
        # jitted batched-step cache keyed by (gba, m, shared_src); shapes
        # are fixed per (config, stream) so each key compiles once
        self._step_cache: dict[tuple, Any] = {}

    # -- batched global step -------------------------------------------------

    def _flat_ids(self, batches: dict, m: int) -> jax.Array:
        """All hashed IDs each slot touched: (M, n_ids)."""
        return jax.vmap(R.flat_ids)(batches).reshape(m, -1)

    def _slot_loss_grads(self, params: Params, batch: dict):
        """One slot's loss of the model's own training objective (DIEN's
        carries its auxiliary term) and ``(dense, ids, rows)``: the
        gradients of the dense leaves, the ids the slot looked up
        (``R.flat_ids``) and the gradients of the rows it gathered for
        them (``R.lookup``)."""
        rows = R.lookup(params, batch)
        dense = {k: v for k, v in params.items() if k not in EMBED_KEYS}
        loss, (dense_g, row_g) = jax.value_and_grad(
            lambda d, r: R.recsys_loss_from_rows(d, self.cfg, r, batch),
            argnums=(0, 1))(dense, rows)
        return loss, (dense_g, R.flat_ids(batch), row_g)

    def _make_step(self, gba: bool, m: int, shared_src: bool):
        """Build the jitted per-global-step function.

        ``shared_src``: every slot dispatched at the same parameter version
        (sync-like schedules) — the gradients vmap over batches only, with
        the params broadcast, skipping the M-way parameter stack.

        The sparse module's gradient is the sum over every (slot, id) row
        of the step of the row's gradient times its factor, Alg. 2 at that
        pair: under GBA 1 for a slot within iota, else whether the id is
        untouched since the slot's token (the per-ID relaxation); in the
        other modes the slot's weight.  The sum is one scatter of the
        sparse leaves side by side, which also sums each id's factor over
        the slots that touched it, its contributor count: the streamed
        sorted-scatter kernel with ``embed_stream`` set, else XLA
        scatters.  It runs under ``jax.named_scope("embedding")``, the
        rest of the GBA aggregation under ``"aggregate"``, and the
        optimizer and ``last_update`` stamp under ``"apply"``; the models
        name their own ``embedding`` and ``dense`` ops.
        """
        cap = self.cfg.hash_capacity
        iota = self.iota
        opt_update = self.optimizer.update
        grad_fn = self._loss_grad_fn
        stream = self.embed_stream
        in_axes = (None, 0) if shared_src else (0, 0)

        def aggregate(dense_g, ids, tokens, weights, step_k, last_update):
            # dense module: Alg. 2 line 22 — weighted sum / N_a (= m)
            wm = (weights / m).astype(jnp.float32)
            agg = jax.tree.map(
                lambda g: jnp.tensordot(wm, g.astype(jnp.float32),
                                        axes=(0, 0)).astype(g.dtype),
                dense_g)

            # sparse module: each (slot, id) row's factor (Alg. 2 lines
            # 21/23), ids (M, n_ids)
            if gba:
                # per-ID relaxation: a slot dropped by Eq.(1) may still
                # contribute rows whose IDs were untouched since its token;
                # a step with no slot past iota keeps every row and reads
                # no last_update
                slot_ok = (step_k - tokens) <= iota             # (M,)
                factor = jax.lax.cond(
                    jnp.all(slot_ok),
                    lambda: jnp.ones(ids.shape, jnp.float32),
                    lambda: jnp.where(
                        slot_ok[:, None], 1.0,
                        (last_update[ids] <= tokens[:, None]
                         ).astype(jnp.float32)))
                rescued = jnp.sum(
                    ((~slot_ok) & jnp.any(factor > 0, axis=1)
                     ).astype(jnp.int32))
            else:
                factor = jnp.broadcast_to(weights[:, None], ids.shape)
                rescued = jnp.int32(0)
            return agg, factor, rescued

        def scatter(ids, factor, row_g, params):
            """Each sparse leaf's sum of factor x row gradient, as one
            scatter of the leaves' rows side by side, and each id's
            contributor count: the sum of ``factor`` over the distinct
            slots that touched it (Alg. 2 line 23), not its occurrence
            count.  ``ids`` is (M, B, n_ids), each leaf's rows
            ``ids.shape + leaf row``."""
            cols = {k: g.reshape(ids.shape + (-1,)) for k, g in row_g.items()}
            factor = factor.reshape(ids.shape)
            rows = jnp.concatenate(list(cols.values()), axis=-1) * \
                factor[..., None]
            if stream is None:
                table, _ = sparse_grads_to_dense(ids, rows, cap)
                # factor is one value a (slot, id) pair, and >= 0
                flat = ids.reshape(m, -1)
                emb_cnt = jnp.zeros((m, cap), jnp.float32).at[
                    jnp.arange(m)[:, None], flat].max(
                        factor.reshape(m, -1)).sum(axis=0)
            else:
                table, emb_cnt = scatter_rows(ids, rows, cap, factor,
                                              stream=stream)
            out, start = {}, 0
            for k, c in cols.items():
                width = c.shape[-1]
                out[k] = table[:, start:start + width].reshape(
                    params[k].shape)
                start += width
            return out, emb_cnt

        def step(src_params, params, opt_state, batches, tokens, weights,
                 step_k, last_update):
            losses, (dense_g, ids, row_g) = jax.vmap(
                grad_fn, in_axes=in_axes)(src_params, batches)
            with jax.named_scope("aggregate"):
                grads, factor, rescued = aggregate(
                    dense_g, ids.reshape(m, -1), tokens, weights, step_k,
                    last_update)
            with jax.named_scope("embedding"):
                table_g, emb_cnt = scatter(ids, factor, row_g, params)
            with jax.named_scope("aggregate"):
                # divide by #slots that touched the ID (Alg. 2 line 23);
                # baselines divide by the same rule for parity
                cntc = jnp.maximum(emb_cnt, 1.0)
                for name, g in table_g.items():
                    grads[name] = g / (cntc[:, None] if g.ndim > 1
                                       else cntc)
            with jax.named_scope("apply"):
                params, opt_state = opt_update(params, grads, opt_state)
                last_update = jnp.where(emb_cnt > 0, step_k, last_update)
            return params, opt_state, last_update, losses, rescued

        return jax.jit(step)

    def _get_step(self, gba: bool, m: int, shared_src: bool):
        key = (gba, m, shared_src)
        if key not in self._step_cache:
            self._step_cache[key] = self._make_step(gba, m, shared_src)
        return self._step_cache[key]

    # -- schedule replay -----------------------------------------------------

    def replay(self, params: Params, opt_state: Any, schedule: Schedule,
               stream: ClickStream, day: int, *,
               last_update: jax.Array | None = None,
               stats: ReplayStats | None = None):
        """Replay one day's schedule.  Returns (params, opt_state,
        last_update, stats)."""
        stats = stats or ReplayStats()
        if last_update is None:
            last_update = jnp.zeros((self.cfg.hash_capacity,), jnp.int32)
        ring = VersionRing(self.history)
        gba = schedule.mode == "gba" and self.per_id_embedding_decay
        slot_rows = schedule.local_batch * R.ids_per_example(self.cfg)

        for k, slots in enumerate(schedule.steps):
            m = len(slots)
            versions = {s.dispatch_step for s in slots}
            shared_src = len(versions) == 1
            with tracing.span("replay.step", day=day, k=k,
                              stacked=len(versions), rows=m * slot_rows):
                with tracing.span("replay.versions", day=day, k=k):
                    ring.put(k, params)
                    srcs = []
                    for slot in slots:
                        src, clamped = ring.get(slot.dispatch_step)
                        stats.history_clamps += int(clamped)
                        srcs.append(src)
                    if shared_src:
                        src_params = srcs[0]
                    else:
                        src_params = _stack_versions(tuple(srcs))
                        stats.stacked_steps += 1
                with tracing.span("replay.inputs", day=day, k=k):
                    raw = [stream.batch(day, slot.batch_index)
                           for slot in slots]
                    batches = {key: jnp.asarray(np.stack([b[key]
                                                          for b in raw]))
                               for key in raw[0]}
                    tokens = jnp.asarray([s.token for s in slots], jnp.int32)
                    weights = jnp.asarray([s.weight for s in slots],
                                          jnp.float32)
                with tracing.span("replay.dispatch", day=day, k=k):
                    variant = (gba, m, shared_src)
                    if variant not in self._step_cache:
                        stats.step_builds[variant] = stats.applied_steps
                    step_fn = self._get_step(*variant)
                    params, opt_state, last_update, losses, rescued = \
                        step_fn(src_params, params, opt_state, batches,
                                tokens, weights, jnp.int32(k), last_update)
                for slot in slots:
                    if slot.weight > 0:
                        stats.kept_slots += 1
                    else:
                        stats.dropped_slots += 1
                stats.scattered_rows += m * slot_rows
                with tracing.span("replay.readback", day=day, k=k):
                    stats.embed_rows_rescued += int(rescued)
                    stats.losses.append(float(jnp.mean(losses)))
                stats.applied_steps += 1
        return params, opt_state, last_update, stats


def evaluate(params: Params, cfg: RecsysConfig, stream: ClickStream,
             day: int, num_batches: int = 16) -> float:
    logit_fn = jax.jit(lambda p, b: R.recsys_logit(p, cfg, b))
    sauc = StreamingAUC()
    for i in range(num_batches):
        batch = stream.batch(day, 10_000 + i)
        sauc.update(batch["label"], np.asarray(logit_fn(params, batch)))
    return sauc.compute()
