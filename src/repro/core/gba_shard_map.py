"""GBA with explicit collectives via shard_map (one PS worker per device).

The pjit train step (launch.steps) treats the whole pod as ONE worker
filling the M-slot buffer over time.  This module expresses the orthogonal
mapping: every device group along the `data` axis is its own worker, each
carrying its OWN token, and one global step aggregates all M = |data|
worker gradients with the token-control decay — Algorithm 2 as a single
`lax.psum` of pre-decayed gradients:

    agg = psum_m( f(token_m, k) * grad_m / M )

which is exactly ``aggregate_dense`` (tested equivalent), but with the
collective schedule explicit — the form you deploy when worker batches
genuinely differ per device (e.g. heterogeneous data streams).

:func:`make_gba_fused_psum_step` is the fused rendering of the same
mapping: every device doubles as a PS shard owning a contiguous
tile-aligned slice of the flat parameter vector
(``core.flat_sharded.ShardedFlatLayout``).  The collective schedule is
**layer-grouped**: parameters are gathered one layer group at a time for
the forward, and each group's gradient is routed to its owning shards by
its own ``all_to_all`` — issued as soon as the backward materializes that
group's gradient, so routing overlaps the remaining backward compute
instead of serializing one monolithic exchange after it.  Peak live
gathered bytes per device is the LARGEST group
(``layout.peak_gather_bytes``), not the whole parameter vector — the
property that lets a PS shard serve models larger than one device's
gather budget.  A single-group layout (``group_by=None``) degenerates to
the PR-4 full-vector schedule, which the parity tests use as the
bit-exactness oracle.  Either way the per-shard apply stays ONE
``gba_apply`` launch (token-decay aggregation + Adagrad in one VMEM pass)
on the contiguous ``(M, shard_size)`` slice; the only ``psum`` left is
the scalar loss.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.compression import CompressionPolicy
from repro.core.flat_sharded import ShardedFlatLayout
from repro.core.staleness import threshold_decay


def make_gba_psum_step(mesh: Mesh, loss_fn: Callable, optimizer,
                       iota: int, axis: str = "data"):
    """Returns step(params, opt_state, batch, tokens, gstep) ->
    (params, opt_state, loss).

    batch: pytree with leading GLOBAL batch dim sharded over ``axis``;
    tokens: (M,) int32, one per worker (device group along ``axis``).
    """
    m = mesh.shape[axis]

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P()),
        out_specs=(P(), P()),
        check_vma=False)
    def grad_agg(params, batch, token, gstep):
        loss, g = jax.value_and_grad(loss_fn)(params, batch)
        w = threshold_decay(token.reshape(-1)[:1], gstep, iota)[0]
        g = jax.tree.map(lambda x: x * (w / m).astype(x.dtype), g)
        g = lax.psum(g, axis)              # decayed aggregate (Alg. 2 l.22)
        loss = lax.psum(loss * w, axis) / m
        return g, loss

    def step(params, opt_state, batch, tokens, gstep):
        agg, loss = grad_agg(params, batch, tokens, gstep)
        params, opt_state = optimizer.update(params, agg, opt_state)
        return params, opt_state, loss

    return step


def make_gba_fused_psum_step(mesh: Mesh, loss_fn: Callable,
                             layout: ShardedFlatLayout, *, iota: int,
                             lr: float, eps: float = 1e-10,
                             axis: str = "data",
                             interpret: bool | None = None,
                             compress: CompressionPolicy | None = None,
                             warm: bool = False):
    """Layer-grouped fused PS rendering of :func:`make_gba_psum_step`
    (Adagrad only), with an optional quantized wire.

    Without compression (``compress=None`` or scheme ``"none"``) returns
    ``step(param_flat, accum_flat, batch, tokens, gstep) ->
    (new_param_flat, new_accum_flat, loss)`` — the PR-5 schedule,
    bit-identical.  With a lossy ``CompressionPolicy`` the step carries
    per-worker wire state and becomes ``step(param_flat, accum_flat,
    batch, tokens, gstep, wire) -> (new_param_flat, new_accum_flat, loss,
    new_wire)`` where ``wire`` holds ``(M, padded_total)`` f32 rows
    (``residual`` always; ``momentum`` for onebit), row ``w`` = worker
    ``w``'s state, sharded ``P(axis, None)``.  ``param_flat`` /
    ``accum_flat`` are the layout's ``(padded_total,)`` vectors sharded
    ``P(axis)`` and ``tokens`` is (M,) — one per worker, M = mesh
    ``axis`` size.

    Collective schedule per global step, with G = ``layout.num_groups``
    layer groups — **gather → grad → compress → route → dequant →
    apply**:

    1. per layer group ``g``: ``all_gather`` that group's param
       sub-slices just-in-time for the forward (``group_sizes[g]`` f32
       per device per group; params always travel full precision).  The
       gathers are G independent ops, each feeding only its group's
       layers, so peak LIVE gathered bytes is
       ``layout.peak_gather_bytes`` (the largest group), not the
       ``padded_total`` a monolithic gather pins;
    2. each worker grads its OWN batch shard with its OWN token, against
       the gathered (not the sharded) params — gradients stay per-worker,
       never summed;
    3. **compress** (lossy schemes, past warmup): worker ``w`` views its
       wire-state rows as ``(num_shards, shard_size)`` — the layout is
       shard-major, so group ``g``'s residual/momentum is the SAME
       ``group_shard_bounds`` column slice as its gradient block.  The
       payload is ``grad + residual`` (int8) or ``momentum + residual``
       after the EMA update (onebit); one ``quantize`` kernel launch per
       group emits the int8 codes, the per-tile f32 sideband
       (scale/zero-point for min-max, mean-|.| norm for sign), and the
       next residual ``payload - dequantize(codes)`` in the same VMEM
       pass (error feedback costs no extra launch);
    4. **route**: per group, ``all_to_all`` sends worker ``w``'s
       sub-slice ``s`` to shard ``s`` — the PS "write", worker->shard
       only.  On the compressed wire the payload operand is int8
       (``compress.route_bytes`` per group ≈ 0.25x of f32) plus the tiny
       f32 sideband exchange; warmup and ``none`` route one f32
       ``(M, group_shard)`` operand per group, bit-identical to PR-5.
       Each exchange issues as soon as the backward materializes its
       group, overlapping the remaining backward compute;
    5. **dequant**: the receiving shard reconstructs f32 with one
       ``dequantize`` launch per group; concatenating the G per-group
       ``(M, group_shard_sizes[g])`` blocks along columns yields the
       local ``(M, shard_size)`` buffer — contiguous because the layout
       is shard-major;
    6. **apply**: ONE ``gba_apply`` launch per shard fuses
       decay-aggregate + Adagrad on the local slice — quantization never
       touches Eq. (1) token-control semantics, which act on the
       reconstructed buffer;
    7. ``psum`` of the decayed scalar loss — the only cross-shard
       reduction left.

    ``warm=True`` builds the warmup-phase step of a lossy policy: f32
    routing exactly as PR-5 (params/accum/loss bit-exact with the
    uncompressed step), residuals untouched, but the onebit momentum EMA
    already accumulating — the Bagua onebit idiom (full-precision warmup
    for ``compress.warmup_steps`` global steps, then sign-compressed
    momentum).  The warmup→compressed switch is a re-jit by the driver
    (``launch.train``), so each phase's jaxpr carries exactly one wire
    dtype — what the GBA-COLL-005 census rule checks.

    With a single-group layout the per-group collectives collapse to one
    ``all_gather`` + one routing exchange: exactly the PR-4 full-vector
    schedule.
    """
    m = mesh.shape[axis]
    if layout.num_shards != m:
        raise ValueError(
            f"layout has {layout.num_shards} shards but mesh axis "
            f"{axis!r} has {m} devices")
    from repro.kernels import ops

    def gather_params(param_flat):
        # just-in-time per-group gathers: tiled all_gather of shard
        # sub-slices reconstructs each group's contiguous flat because
        # the layout is shard-major within a group
        gathered = []
        for g in range(layout.num_groups):
            lo, hi = layout.group_shard_bounds(g)
            gathered.append(
                lax.all_gather(param_flat[lo:hi], axis, axis=0, tiled=True))
        return layout.unravel_groups(gathered)

    def route(x):
        # worker w's rows = destination shards; all_to_all leaves row w of
        # shard s holding worker w's sub-slice s of THIS group
        return lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                              tiled=True)

    def apply_and_loss(param_flat, accum_flat, bufs, token, gstep, loss):
        buf = bufs[0] if len(bufs) == 1 else jnp.concatenate(bufs, axis=1)
        tokens_all = lax.all_gather(token.reshape(-1)[:1], axis, axis=0,
                                    tiled=True)
        new_p, new_a = ops.gba_apply_flat(
            param_flat, accum_flat, buf, tokens_all, gstep, lr, iota=iota,
            eps=eps, interpret=interpret)
        w = threshold_decay(token.reshape(-1)[:1], gstep, iota)[0]
        return new_p, new_a, lax.psum(loss * w, axis) / m

    if compress is None or not compress.stateful:
        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P(axis), P()),
            out_specs=(P(axis), P(axis), P()),
            check_vma=False)
        def step(param_flat, accum_flat, batch, token, gstep):
            params = gather_params(param_flat)
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            bufs = [route(layout.ravel_group(g, grads).reshape(m, -1))
                    for g in range(layout.num_groups)]
            return apply_and_loss(param_flat, accum_flat, bufs, token,
                                  gstep, loss)

        return step

    scheme = compress.scheme
    mode = "minmax" if scheme == "int8" else "sign"
    beta = compress.momentum
    wire_spec = {name: P(axis, None) for name in compress.state_names()}

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(), wire_spec),
        out_specs=(P(axis), P(axis), P(), wire_spec),
        check_vma=False)
    def step(param_flat, accum_flat, batch, token, gstep, wire):
        params = gather_params(param_flat)
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        # this worker's wire-state rows, viewed shard-major so group g is
        # the same column slice as its gradient block
        res = wire["residual"].reshape(m, layout.shard_size)
        mom = (wire["momentum"].reshape(m, layout.shard_size)
               if scheme == "onebit" else None)
        bufs, new_res, new_mom = [], [], []
        for g in range(layout.num_groups):
            lo, hi = layout.group_shard_bounds(g)
            gm = layout.ravel_group(g, grads).reshape(m, -1)
            if scheme == "onebit":
                mom_g = beta * mom[:, lo:hi] + (1.0 - beta) * gm
                new_mom.append(mom_g)
                src = mom_g
            else:
                src = gm
            if warm:
                # full-precision warmup: route the raw gradient (PR-5
                # bit-exact); residual stays zero, momentum accumulates
                bufs.append(route(gm))
                new_res.append(res[:, lo:hi])
                continue
            payload = src + res[:, lo:hi]
            if mode == "minmax":
                q, sc, zp, r_g = ops.quantize_wire(
                    payload, tile=layout.tile, mode=mode,
                    interpret=interpret)
                deq = ops.dequantize_wire(
                    route(q), route(sc), route(zp), tile=layout.tile,
                    mode=mode, interpret=interpret)
            else:
                q, sc, r_g = ops.quantize_wire(
                    payload, tile=layout.tile, mode=mode,
                    interpret=interpret)
                deq = ops.dequantize_wire(
                    route(q), route(sc), tile=layout.tile, mode=mode,
                    interpret=interpret)
            bufs.append(deq)
            new_res.append(r_g)
        new_wire = {"residual": _recols(new_res, wire["residual"].shape)}
        if scheme == "onebit":
            new_wire["momentum"] = _recols(new_mom,
                                           wire["momentum"].shape)
        new_p, new_a, loss = apply_and_loss(param_flat, accum_flat, bufs,
                                            token, gstep, loss)
        return new_p, new_a, loss, new_wire

    return step


def _recols(cols: list, local_shape) -> jnp.ndarray:
    """Per-group column blocks -> the worker's local wire-state row(s)."""
    out = cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)
    return out.reshape(local_shape)
