"""Sharding-aware flat-buffer GBA: the fused one-launch apply per PS shard.

``core.gba.FlatLayout`` ravels the dense module into one ``(M, N_total)``
buffer so a full-buffer apply is ONE ``repro.kernels.gba_apply`` launch —
but only on a single host: the flat axis carries no sharding, so the
sharded production path kept the per-leaf ``buffer_push_and_maybe_apply``
chain (one aggregate + one optimizer launch per leaf, dozens per global
step).  This module closes that gap:

:class:`ShardedFlatLayout`
    Lays leaves back-to-back like ``FlatLayout`` but pads every leaf to a
    ``tile`` multiple (leaf boundaries coincide with tile boundaries) and
    pads the total so it splits into ``num_shards`` equal, tile-aligned,
    contiguous slices.  Shard ``s`` owns ``flat[s*shard_size :
    (s+1)*shard_size]`` — whole kernel blocks when ``tile`` is the
    ``gba_apply`` block size (the default), so a PS shard's apply never
    straddles a partial tile.

    With ``group_by`` the layout is additionally **layer-grouped**: every
    leaf is assigned to a layer group derived from its pytree path, each
    group's flat extent is contiguous and splits into ``num_shards`` equal
    tile-aligned sub-slices, and the GLOBAL flat ordering is shard-major —
    shard ``s``'s contiguous slice is the concatenation of every group's
    ``s``-th sub-slice.  A layer-grouped collective schedule
    (``core.gba_shard_map.make_gba_fused_psum_step``) can then
    ``all_gather`` one group at a time (peak live gathered bytes =
    :attr:`peak_gather_bytes` = the largest group, not ``N_total``) and
    route each group's gradient with its own ``all_to_all`` while the
    backward still computes the remaining groups — yet the per-shard slice
    stays ONE contiguous run, so the fused apply is still a single
    ``gba_apply`` launch.  ``group_by=None`` (the default) is exactly the
    ungrouped PR-4 layout: one group covering everything, shard-major
    ordering degenerating to plain concatenation.

:func:`make_sharded_apply`
    ``shard_map`` wrapper that runs the single-launch ``gba_apply``
    (token-decay aggregate + Adagrad, one VMEM pass) on each shard's
    slice.  Tokens / global step are replicated, so every shard derives
    the same (M,) decay weights from the broadcast scalars on its scalar
    core; the gradient columns never cross shards — no collective touches
    the buffer at apply time.  Grouping-agnostic: the kernel only sees the
    contiguous local slice.

:func:`sharded_flat_push_and_maybe_apply`
    Drop-in sharded counterpart of
    ``core.gba.flat_buffer_push_and_maybe_apply``: the push is
    elementwise along the flat axis (XLA keeps it local under a
    ``P(None, axis)`` buffer sharding); the apply branch launches the
    shard-mapped kernel.  Bit-exact with the single-host flat path and
    with a per-leaf ``gba_apply`` launch chain (same kernel arithmetic
    per element; see :func:`per_leaf_kernel_apply`).
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.gba import flat_buffer_push
from repro.kernels.gba_apply import BLOCK_N

Params = Any
GroupBy = Callable[[tuple[str, ...]], str]


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def path_names(path) -> tuple[str, ...]:
    """Pytree key path -> name tuple (dict keys, ``#i`` sequence indices,
    attribute names) — the canonical helper behind both the layer
    grouping here and the sharding rules in ``distributed.sharding``."""
    names = []
    for e in path:
        if hasattr(e, "key"):
            names.append(str(e.key))
        elif hasattr(e, "idx"):
            names.append(f"#{e.idx}")
        elif hasattr(e, "name"):
            names.append(str(e.name))
    return tuple(names)


@dataclass(frozen=True)
class ShardedFlatLayout:
    """Leaf-aligned, tile-aligned flat layout split into PS shard slices.

    ``offsets[j]`` (a ``tile`` multiple) is where leaf ``j``'s data starts
    *within its layer group's contiguous flat*; ``padded_sizes[j]`` is its
    tile-rounded extent, zero-filled past ``sizes[j]``.  Group ``g``
    occupies ``group_sizes[g]`` flat elements (a ``num_shards * tile``
    multiple), of which shard ``s`` owns the ``s``-th
    ``group_shard_sizes[g]``-wide sub-slice at local column
    ``group_local_offsets[g]`` of its slice.  ``padded_total ==
    num_shards * shard_size`` and ``shard_size % tile == 0``, so every
    shard's slice starts and ends on a tile boundary regardless of leaf
    shapes.  For the default single-group layout (``group_by=None``) the
    group-local offsets ARE global flat offsets — the PR-4 layout,
    bit-identical.  Host-side object (hashable tuples only) — closable
    over by jitted train steps.
    """

    treedef: Any
    shapes: tuple[tuple[int, ...], ...]
    dtypes: tuple[Any, ...]
    sizes: tuple[int, ...]
    padded_sizes: tuple[int, ...]
    offsets: tuple[int, ...]
    total: int            # sum of true leaf sizes (FlatLayout's N_total)
    padded_total: int     # num_shards * shard_size
    num_shards: int
    shard_size: int
    tile: int
    group_keys: tuple[str, ...]         # group names, in layout order
    leaf_group: tuple[int, ...]         # group index per leaf
    group_sizes: tuple[int, ...]        # padded flat extent per group
    group_shard_sizes: tuple[int, ...]  # = group_sizes[g] // num_shards
    group_local_offsets: tuple[int, ...]  # column of group g in a shard

    @classmethod
    def from_params(cls, params: Params, num_shards: int,
                    tile: int = BLOCK_N,
                    group_by: GroupBy | None = None) -> "ShardedFlatLayout":
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        if tile < 1:
            raise ValueError(f"tile must be >= 1, got {tile}")
        path_leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
        paths = tuple(path_names(p) for p, _ in path_leaves)
        leaves = [l for _, l in path_leaves]
        shapes = tuple(tuple(l.shape) for l in leaves)
        dtypes = tuple(jnp.dtype(l.dtype) for l in leaves)
        sizes = tuple(math.prod(s) for s in shapes)
        padded_sizes = tuple(_round_up(s, tile) for s in sizes)
        keys = (["all"] * len(leaves) if group_by is None
                else [str(group_by(p)) for p in paths])
        group_keys: list[str] = []
        leaf_group: list[int] = []
        for k in keys:                       # group order = first appearance
            if k not in group_keys:
                group_keys.append(k)
            leaf_group.append(group_keys.index(k))
        if not group_keys:
            group_keys = ["all"]             # empty-params edge case
        # group-local leaf offsets (treedef order within each group)
        offsets, cursor = [], [0] * len(group_keys)
        for j, g in enumerate(leaf_group):
            offsets.append(cursor[g])
            cursor[g] += padded_sizes[j]
        chunk = num_shards * tile
        group_sizes = tuple(_round_up(max(c, tile), chunk) for c in cursor)
        group_shard_sizes = tuple(gs // num_shards for gs in group_sizes)
        group_local_offsets, col = [], 0
        for gsn in group_shard_sizes:
            group_local_offsets.append(col)
            col += gsn
        shard_size = col
        return cls(treedef, shapes, dtypes, sizes, padded_sizes,
                   tuple(offsets), sum(sizes), num_shards * shard_size,
                   num_shards, shard_size, tile, tuple(group_keys),
                   tuple(leaf_group), group_sizes, group_shard_sizes,
                   tuple(group_local_offsets))

    # -- group geometry -----------------------------------------------------
    @property
    def num_groups(self) -> int:
        return len(self.group_keys)

    @property
    def peak_gather_bytes(self) -> int:
        """Per-device peak live gathered bytes of the layer-grouped
        schedule: the largest single group's f32 extent (vs
        :attr:`full_gather_bytes` for the ungrouped full-vector gather)."""
        return max(self.group_sizes) * 4

    @property
    def full_gather_bytes(self) -> int:
        """Per-device gathered bytes of the full-vector (PR-4) schedule."""
        return self.padded_total * 4

    def group_shard_bounds(self, g: int) -> tuple[int, int]:
        """[start, stop) columns of group ``g`` within one shard's local
        ``(shard_size,)`` slice (host ints)."""
        if not 0 <= g < self.num_groups:
            raise IndexError(g)
        lo = self.group_local_offsets[g]
        return lo, lo + self.group_shard_sizes[g]

    def group_leaves(self, g: int) -> tuple[int, ...]:
        """Leaf indices belonging to group ``g``, in treedef order."""
        return tuple(j for j, lg in enumerate(self.leaf_group) if lg == g)

    def group_table(self, compress=None) -> list[dict]:
        """Host-side summary, one entry per group (for logs / benches).

        With a ``CompressionPolicy`` (``core.compression``), each entry
        additionally reports the group's routed ``wire_bytes`` (payload +
        per-tile sideband) and ``wire_dtype`` under that policy; without
        one the wire is the full-precision f32 routing (``wire_bytes ==
        bytes``)."""
        rows = []
        for g, k in enumerate(self.group_keys):
            row = {"key": k,
                   "elements": self.group_sizes[g],
                   "bytes": self.group_sizes[g] * 4,
                   "leaves": len(self.group_leaves(g))}
            if compress is None:
                row["wire_bytes"] = row["bytes"]
                row["wire_dtype"] = "float32"
            else:
                row["wire_bytes"] = compress.route_bytes(
                    self.group_sizes[g], self.tile)
                row["wire_dtype"] = compress.wire_dtype()
            rows.append(row)
        return rows

    def wire_state_shapes(self, m: int, scheme: str) -> dict:
        """Shapes of the per-worker wire-compression state (error-feedback
        residual, onebit momentum): one ``(m, padded_total)`` f32 row per
        worker, columns in this layout's shard-major order so per-group
        views are the :meth:`group_shard_bounds` column slices the routing
        stage already uses."""
        names = {"none": (), "int8": ("residual",),
                 "onebit": ("residual", "momentum")}
        if scheme not in names:
            raise ValueError(f"unknown compression scheme {scheme!r}")
        return {name: (m, self.padded_total) for name in names[scheme]}

    # -- ravel / unravel ----------------------------------------------------
    def ravel_group(self, g: int, tree: Params) -> jax.Array:
        """Group ``g``'s leaves of ``tree`` -> contiguous
        ``(group_sizes[g],)`` f32; per-leaf tail padding is zero so padding
        columns never contribute gradient (Adagrad on a zero grad is the
        identity)."""
        leaves = jax.tree.leaves(tree)
        parts, used = [], 0
        for j in self.group_leaves(g):
            flat = leaves[j].reshape(-1).astype(jnp.float32)
            if self.padded_sizes[j] > self.sizes[j]:
                flat = jnp.pad(flat, (0, self.padded_sizes[j]
                                      - self.sizes[j]))
            parts.append(flat)
            used += self.padded_sizes[j]
        tail = self.group_sizes[g] - used
        if tail:
            parts.append(jnp.zeros((tail,), jnp.float32))
        return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

    def unravel_group(self, g: int, group_flat: jax.Array,
                      dtype=None) -> list:
        """Contiguous group flat -> that group's leaves (treedef order).
        ``dtype`` overrides the per-leaf cast — e.g. ``jnp.float32`` when
        unraveling an OPTIMIZER vector (Adagrad accum) whose leaves must
        stay f32 even for a bf16-param model."""
        return [
            group_flat[self.offsets[j]:self.offsets[j] + self.sizes[j]]
            .reshape(self.shapes[j])
            .astype(self.dtypes[j] if dtype is None else dtype)
            for j in self.group_leaves(g)]

    def unravel_groups(self, group_flats: list[jax.Array],
                       dtype=None) -> Params:
        """Per-group contiguous flats -> the full pytree."""
        leaves: list = [None] * len(self.sizes)
        for g, gflat in enumerate(group_flats):
            for j, leaf in zip(self.group_leaves(g),
                               self.unravel_group(g, gflat, dtype)):
                leaves[j] = leaf
        return jax.tree.unflatten(self.treedef, leaves)

    def ravel(self, tree: Params) -> jax.Array:
        """Pytree -> (padded_total,) f32 in shard-major group order: shard
        ``s``'s slice is the concatenation of every group's ``s``-th
        sub-slice.  Single-group layouts reduce to plain concatenation
        (the PR-4 ordering, bit-identical)."""
        gfs = [self.ravel_group(g, tree).reshape(self.num_shards, -1)
               for g in range(self.num_groups)]
        if len(gfs) == 1:
            return gfs[0].reshape(-1)
        return jnp.concatenate(gfs, axis=1).reshape(-1)

    def unravel(self, flat: jax.Array, dtype=None) -> Params:
        rows = flat.reshape(self.num_shards, self.shard_size)
        gfs = [rows[:, lo:lo + gsn].reshape(-1)
               for lo, gsn in zip(self.group_local_offsets,
                                  self.group_shard_sizes)]
        return self.unravel_groups(gfs, dtype)

    # -- shard geometry -----------------------------------------------------
    def shard_bounds(self, s: int) -> tuple[int, int]:
        """[start, stop) of shard ``s``'s flat slice (host ints)."""
        if not 0 <= s < self.num_shards:
            raise IndexError(s)
        return s * self.shard_size, (s + 1) * self.shard_size

    def leaves_in_shard(self, s: int) -> tuple[int, ...]:
        """Leaf indices whose (padded) extent overlaps shard ``s`` — what
        a per-leaf chain would have to launch on this shard."""
        lo, hi = self.shard_bounds(s)
        out = []
        for j, (off, n) in enumerate(zip(self.offsets, self.padded_sizes)):
            gsn = self.group_shard_sizes[self.leaf_group[j]]
            # leaf j spans [off, off+n) of its group flat; shard s owns
            # [s*gsn, (s+1)*gsn) of that group
            if off < (s + 1) * gsn and off + n > s * gsn:
                out.append(j)
        return tuple(out)


def init_sharded_flat_buffer(params: Params, buffer_size: int,
                             num_shards: int, tile: int = BLOCK_N,
                             group_by: GroupBy | None = None
                             ) -> tuple[ShardedFlatLayout, dict]:
    """Sharded flat M-slot buffer: ``grads`` is ``(M, padded_total)`` and
    meant to live under a ``P(None, axis)`` sharding (columns split across
    PS shards, slots replicated).  ``group_by`` opts into the layer-grouped
    layout (see :class:`ShardedFlatLayout`)."""
    layout = ShardedFlatLayout.from_params(params, num_shards, tile,
                                           group_by=group_by)
    return layout, {
        "grads": jnp.zeros((buffer_size, layout.padded_total), jnp.float32),
        "tokens": jnp.zeros((buffer_size,), jnp.int32),
        "fill": jnp.zeros((), jnp.int32),
        "step": jnp.zeros((), jnp.int32),
    }


def make_sharded_apply(mesh: Mesh, layout: ShardedFlatLayout, *,
                       axis: str = "data", iota: int, eps: float = 1e-10,
                       interpret: bool | None = None):
    """shard_map'd single-launch apply: each PS shard runs ``gba_apply``
    on its contiguous ``(M, shard_size)`` buffer slice.

    Returns ``apply(param_flat, accum_flat, grads, tokens, step, lr) ->
    (new_param_flat, new_accum_flat)`` over GLOBAL ``(padded_total,)`` /
    ``(M, padded_total)`` arrays.  Tokens/step/lr are broadcast (``P()``)
    — the decay weights are computed once from them on every shard's
    scalar core; no collective touches the gradient columns.
    """
    if layout.num_shards != mesh.shape[axis]:
        raise ValueError(
            f"layout has {layout.num_shards} shards but mesh axis "
            f"{axis!r} has {mesh.shape[axis]} devices")
    from repro.kernels import ops

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axis), P(axis), P(None, axis), P(), P(), P()),
        out_specs=(P(axis), P(axis)),
        check_vma=False)
    def apply_shards(param_flat, accum_flat, grads, tokens, step, lr):
        return ops.gba_apply_flat(param_flat, accum_flat, grads, tokens,
                                  step, lr, iota=iota, eps=eps,
                                  interpret=interpret)

    return apply_shards


def sharded_flat_push_and_maybe_apply(
        buffer: dict, flat_grad: jax.Array, token: jax.Array,
        param_flat: jax.Array, accum_flat: jax.Array, lr, *, mesh: Mesh,
        layout: ShardedFlatLayout, axis: str = "data", iota: int,
        eps: float = 1e-10, interpret: bool | None = None):
    """Sharded counterpart of ``core.gba.flat_buffer_push_and_maybe_apply``.

    The push is elementwise along the flat axis, so under a
    ``P(None, axis)`` buffer sharding XLA keeps it communication-free; the
    apply branch is one shard-mapped ``gba_apply`` launch per PS shard.
    Returns ``(new_param_flat, new_accum_flat, applied, new_buffer)`` —
    the partial-buffer branch passes params/accum through untouched.
    """
    new_buffer, is_full = flat_buffer_push(buffer, flat_grad, token)
    apply_shards = make_sharded_apply(mesh, layout, axis=axis, iota=iota,
                                      eps=eps, interpret=interpret)

    def do_apply(operands):
        p, a, grads, tokens, step, lr_ = operands
        return apply_shards(p, a, grads, tokens, step, lr_)

    def do_noop(operands):
        p, a, *_ = operands
        return p, a

    new_param, new_accum = jax.lax.cond(
        is_full, do_apply, do_noop,
        (param_flat, accum_flat, new_buffer["grads"], new_buffer["tokens"],
         buffer["step"], jnp.asarray(lr, jnp.float32)))
    return new_param, new_accum, is_full, new_buffer


def per_leaf_kernel_apply(layout: ShardedFlatLayout, param_flat: jax.Array,
                          accum_flat: jax.Array, grads: jax.Array,
                          tokens: jax.Array, step: jax.Array, lr, *,
                          iota: int, eps: float = 1e-10,
                          interpret: bool | None = None
                          ) -> tuple[jax.Array, jax.Array]:
    """The per-leaf launch chain the sharded apply replaces: one
    ``gba_apply`` call per leaf slice (``len(layout.sizes)`` launches vs
    one per shard).  Kernel arithmetic is identical per element, so this
    is the bit-exactness oracle for the fused sharded path — and the
    launch-count baseline for ``benchmarks.bench_kernels``.  Single-group
    layouts only: a layer-grouped layout interleaves leaves shard-major,
    so no leaf is one contiguous global run."""
    if layout.num_groups > 1:
        raise ValueError(
            "per_leaf_kernel_apply requires a single-group layout; "
            f"got {layout.num_groups} groups {layout.group_keys}")
    from repro.kernels import ops
    new_p, new_a = param_flat, accum_flat
    for off, size in zip(layout.offsets, layout.sizes):
        lp, la = ops.gba_apply_flat(
            param_flat[off:off + size], accum_flat[off:off + size],
            grads[:, off:off + size], tokens, step, lr, iota=iota, eps=eps,
            interpret=interpret)
        new_p = jax.lax.dynamic_update_slice(new_p, lp, (off,))
        new_a = jax.lax.dynamic_update_slice(new_a, la, (off,))
    return new_p, new_a
