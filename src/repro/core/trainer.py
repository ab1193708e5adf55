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
inside the compiled step.  The previous implementation dispatched M
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
from repro.embeddings.table import StreamConfig, presence_counts
from repro.metrics import StreamingAUC
from repro.models import recsys as R
from repro.optim import Optimizer
from repro.sim.cluster import Schedule

Params = Any

EMBED_KEYS = ("embed", "linear")   # the sparse module (DESIGN.md §2)


@dataclass
class ReplayStats:
    applied_steps: int = 0
    kept_slots: int = 0
    dropped_slots: int = 0
    history_clamps: int = 0
    embed_rows_rescued: int = 0     # per-ID relaxation kept a stale slot's row
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


def _split_tree(grads: Params) -> tuple[Params, Params]:
    sparse = {k: v for k, v in grads.items() if k in EMBED_KEYS}
    dense = {k: v for k, v in grads.items() if k not in EMBED_KEYS}
    return sparse, dense


@dataclass
class GBATrainer:
    cfg: RecsysConfig
    optimizer: Optimizer
    iota: int = 4
    per_id_embedding_decay: bool = True   # Alg. 2 lines 21/23
    history: int = 64
    # production-capacity knob: when set, the per-slot presence counts come
    # from the streamed sorted-scatter kernel (O(block) VMEM at any
    # hash_capacity) instead of an XLA one-hot scatter per slot
    embed_stream: StreamConfig | None = None

    def __post_init__(self):
        # the model's own training loss (DIEN's carries its auxiliary term)
        self._loss_grad_fn = jax.value_and_grad(
            lambda p, b: R.recsys_loss(p, self.cfg, b))
        # jitted batched-step cache keyed by (gba, m, shared_src); shapes
        # are fixed per (config, stream) so each key compiles once
        self._step_cache: dict[tuple, Any] = {}

    # -- batched global step -------------------------------------------------

    def _flat_ids(self, batches: dict, m: int) -> jax.Array:
        """All hashed IDs each slot touched: (M, n_ids)."""
        parts = [batches["fields"].reshape(m, -1)]
        if "behavior" in batches:
            parts.append(batches["behavior"].reshape(m, -1))
            parts.append(batches["target"].reshape(m, -1))
        return jnp.concatenate(parts, axis=1)

    def _make_step(self, gba: bool, m: int, shared_src: bool):
        """Build the jitted per-global-step function.

        ``shared_src``: every slot dispatched at the same parameter version
        (sync-like schedules) — the gradients vmap over batches only, with
        the params broadcast, skipping the M-way parameter stack.

        The GBA aggregation runs under ``jax.named_scope("aggregate")`` and
        the optimizer and ``last_update`` stamp under ``"apply"``; the
        models name their own ``embedding`` and ``dense`` ops.
        """
        cap = self.cfg.hash_capacity
        iota = self.iota
        opt_update = self.optimizer.update
        grad_fn = self._loss_grad_fn
        in_axes = (None, 0) if shared_src else (0, 0)

        def aggregate(sparse_g, dense_g, batches, tokens, weights, step_k,
                      last_update):
            # dense module: Alg. 2 line 22 — weighted sum / N_a (= m)
            wm = (weights / m).astype(jnp.float32)
            agg = jax.tree.map(
                lambda g: jnp.tensordot(wm, g.astype(jnp.float32),
                                        axes=(0, 0)).astype(g.dtype),
                dense_g)

            # sparse module: per-ID treatment (Alg. 2 lines 21/23)
            ids_all = self._flat_ids(batches, m)
            if self.embed_stream is not None:
                # streamed counts: offsetting slot i's ids by i*cap turns
                # the M per-slot histograms into ONE sorted-scatter kernel
                # launch over an (M*cap)-row id space — a single sort, no
                # XLA one-hot scatter, O(block) VMEM at any capacity
                slot_offset = (jnp.arange(m, dtype=jnp.int32) * cap)[:, None]
                present = presence_counts(
                    ids_all + slot_offset, m * cap,
                    stream=self.embed_stream).reshape(m, cap)
            else:
                present = jax.vmap(
                    lambda ids: jnp.zeros((cap,),
                                          jnp.float32).at[ids].add(1.0)
                )(ids_all)
            touched01 = (present > 0).astype(jnp.float32)       # (M, cap)
            rescued = jnp.int32(0)
            if gba:
                # per-ID relaxation: a slot dropped by Eq.(1) may still
                # contribute rows whose IDs were untouched since its token
                slot_ok = (step_k - tokens) <= iota             # (M,)
                id_fresh = last_update[None, :] <= tokens[:, None]
                keep_row = jnp.where(slot_ok[:, None], 1.0,
                                     id_fresh.astype(jnp.float32))
                row_mask = touched01 * keep_row                 # (M, cap)
                rescued = jnp.sum(
                    ((~slot_ok) & (jnp.sum(row_mask, axis=1) > 0)
                     ).astype(jnp.int32))
                emb_num = {
                    name: jnp.sum(
                        g * (row_mask[..., None] if g.ndim == 3
                             else row_mask), axis=0)
                    for name, g in sparse_g.items()
                }
                emb_cnt = jnp.sum(row_mask, axis=0)
            else:
                # same denominator semantics as the GBA path: an ID's
                # contributor count is the number of SLOTS that touched
                # it (Alg. 2 line 23), not its occurrence count
                emb_num = {
                    name: jnp.tensordot(weights, g, axes=(0, 0))
                    for name, g in sparse_g.items()
                }
                emb_cnt = jnp.sum(touched01 * weights[:, None], axis=0)

            # embedding aggregate: divide by #slots that touched the ID
            # (Alg. 2 line 23); baselines divide by the same rule for parity
            full_grads = dict(agg)
            cntc = jnp.maximum(emb_cnt, 1.0)
            for name, g in emb_num.items():
                full_grads[name] = g / (cntc[:, None] if g.ndim > 1
                                        else cntc)
            return full_grads, emb_cnt, rescued

        def step(src_params, params, opt_state, batches, tokens, weights,
                 step_k, last_update):
            losses, grads = jax.vmap(grad_fn, in_axes=in_axes)(
                src_params, batches)
            sparse_g, dense_g = _split_tree(grads)
            with jax.named_scope("aggregate"):
                full_grads, emb_cnt, rescued = aggregate(
                    sparse_g, dense_g, batches, tokens, weights, step_k,
                    last_update)
            with jax.named_scope("apply"):
                params, opt_state = opt_update(params, full_grads, opt_state)
                if sparse_g:
                    touched = emb_cnt > 0
                    last_update = jnp.where(touched, step_k, last_update)
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

        for k, slots in enumerate(schedule.steps):
            m = len(slots)
            versions = {s.dispatch_step for s in slots}
            shared_src = len(versions) == 1
            with tracing.span("replay.step", day=day, k=k,
                              stacked=len(versions)):
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
