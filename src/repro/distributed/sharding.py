"""Sharding rules: params / caches / batches -> PartitionSpec trees.

Scheme: 2-D "fsdp + tensor" sharding on the single-pod
(data=16, model=16) mesh —

  weight matrices    rows over ``data`` (FSDP), cols over ``model`` (TP)
  attention heads    q/kv head axis over ``model`` (hd fallback when the
                     head count does not divide, e.g. starcoder2's 24H)
  MoE experts        expert axis over ``model`` (expert parallel), d_model
                     over ``data`` (FSDP) — the 1T kimi-k2 needs both
  embeddings/vocab   rows over ``model``, dim over ``data``
  norms/scalars      replicated

The multi-pod mesh adds a ``pod`` axis used purely for data parallelism:
params replicated across pods (DCN carries only gradient all-reduces),
batch sharded over ``(pod, data)``.

Every rule degrades to ``None`` when the dimension does not divide the mesh
axis, so one engine covers all ten architectures.  GBA state (gradient
buffer / accumulator) shards exactly like its gradient — the paper's
"each PS owns the buffer of its partition" mapped onto SPMD.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.flat_sharded import path_names as _path_names


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis]


def _fits(dim: int, mesh: Mesh, axis: str) -> bool:
    return dim % _axis_size(mesh, axis) == 0


def _maybe(dim: int, mesh: Mesh, axis: str) -> str | None:
    return axis if _fits(dim, mesh, axis) else None


def _leaf_spec(names: list[str], shape: tuple[int, ...], mesh: Mesh) -> P:
    """Trailing-dims rule table; leading stacked dims (scan repeats, GBA
    buffer slots) are replicated."""
    name = names[-1] if names else ""
    parent = names[-2] if len(names) > 1 else ""
    stacked = sum(1 for n in names if n in ("blocks", "encoder"))
    # GBA buffer / stacked-grad leading axis is handled by the caller
    # passing the unstacked shape; here stacked == scan repeats only.
    core = shape[stacked:]
    lead = (None,) * stacked

    def spec(*dims):
        return P(*lead, *dims)

    if name in ("embed",):
        return spec(_maybe(core[0], mesh, "model"),
                    _maybe(core[1], mesh, "data"))
    if name == "lm_head":
        return spec(_maybe(core[0], mesh, "data"),
                    _maybe(core[1], mesh, "model"))
    if parent == "moe":                                     # expert parallel
        if name == "router":
            return spec(None, _maybe(core[1], mesh, "model"))
        if name in ("wi_gate", "wi_up"):
            e, d, f = core
            return spec(_maybe(e, mesh, "model"),
                        _maybe(d, mesh, "data"), None)
        if name == "wo":
            e, f, d = core
            return spec(_maybe(e, mesh, "model"), None,
                        _maybe(d, mesh, "data"))
    if name in ("wq", "wk", "wv") and len(core) == 3:
        d, h, hd = core
        if _fits(h, mesh, "model"):
            return spec(_maybe(d, mesh, "data"), "model", None)
        return spec(_maybe(d, mesh, "data"), None,
                    _maybe(hd, mesh, "model"))
    if name == "wo" and len(core) == 3:                     # attention out
        h, hd, d = core
        if _fits(h, mesh, "model"):
            return spec("model", None, _maybe(d, mesh, "data"))
        return spec(None, _maybe(hd, mesh, "model"),
                    _maybe(d, mesh, "data"))
    if name in ("wi_gate", "wi_up") and len(core) == 2:     # dense mlp
        return spec(_maybe(core[0], mesh, "data"),
                    _maybe(core[1], mesh, "model"))
    if name == "wo" and len(core) == 2:
        return spec(_maybe(core[0], mesh, "model"),
                    _maybe(core[1], mesh, "data"))
    if name == "in_proj":                                   # mamba
        return spec(_maybe(core[0], mesh, "data"),
                    _maybe(core[1], mesh, "model"))
    if name == "out_proj":
        return spec(_maybe(core[0], mesh, "model"),
                    _maybe(core[1], mesh, "data"))
    if name == "conv_w":
        return spec(None, _maybe(core[1], mesh, "model"))
    # norms, biases, A_log, dt_bias, D_skip, scalars
    return spec(*([None] * len(core)))


def param_specs(params_shapes: Any, mesh: Mesh) -> Any:
    """ShapeDtypeStruct pytree -> PartitionSpec pytree."""

    def per_leaf(path, leaf):
        names = _path_names(path)
        sp = _leaf_spec(names, leaf.shape, mesh)
        return sp

    return jax.tree_util.tree_map_with_path(per_leaf, params_shapes)


def stacked_specs(specs: Any, lead: int = 1) -> Any:
    """Prepend ``lead`` replicated dims (M-slot GBA buffer over params)."""
    return jax.tree.map(lambda s: P(*((None,) * lead), *s), specs,
                        is_leaf=lambda s: isinstance(s, P))


# ---------------------------------------------------------------------------
# flat-sharded GBA state (core.flat_sharded.ShardedFlatLayout)
# ---------------------------------------------------------------------------

def flat_slice_specs(layout: Any, mesh: Mesh, axis: str = "data") -> dict:
    """PartitionSpecs for a ShardedFlatLayout's state: flat param/accum
    vectors split over ``axis`` (each PS shard owns one contiguous
    tile-aligned slice), buffer columns likewise with the M slot axis
    replicated, slot tokens / fill / step scalars replicated.  The specs
    are grouping-agnostic — a layer-grouped layout orders the flat axis
    shard-major, so ``P(axis)`` still hands every shard one contiguous
    slice containing its sub-slice of every layer group.

    Validates the layout geometry against the mesh: the layout must have
    exactly one shard per device on ``axis``, its padded total must split
    evenly, and its layer-group table must be self-consistent (every
    group a whole number of ``num_shards * tile`` chunks summing to the
    padded total, every leaf assigned to a real group).  All guaranteed
    by ``ShardedFlatLayout.from_params``; re-checked here so a stale or
    hand-built layout fails loudly at spec-build time rather than as an
    XLA shape error inside shard_map.
    """
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has no axis {axis!r}: {mesh.axis_names}")
    n_dev = _axis_size(mesh, axis)
    if layout.num_shards != n_dev:
        raise ValueError(
            f"layout has {layout.num_shards} shards, mesh axis {axis!r} "
            f"has {n_dev} devices")
    if layout.padded_total != layout.num_shards * layout.shard_size:
        raise ValueError(
            f"layout padded_total {layout.padded_total} != "
            f"{layout.num_shards} * {layout.shard_size}")
    chunk = layout.num_shards * layout.tile
    for key, gs in zip(layout.group_keys, layout.group_sizes):
        if gs % chunk:
            raise ValueError(
                f"layer group {key!r} extent {gs} is not a multiple of "
                f"num_shards * tile = {chunk}")
    if sum(layout.group_sizes) != layout.padded_total:
        raise ValueError(
            f"layer groups cover {sum(layout.group_sizes)} elements, "
            f"layout padded_total is {layout.padded_total}")
    if any(g >= len(layout.group_keys) for g in layout.leaf_group):
        raise ValueError("leaf_group indexes past the group table")
    return {
        "flat": P(axis),
        "buffer": {
            "grads": P(None, axis),
            "tokens": P(),
            "fill": P(),
            "step": P(),
        },
    }


def wire_state_specs(layout: Any, mesh: Mesh, scheme: str,
                     axis: str = "data") -> dict:
    """PartitionSpecs for the compressed-wire state of
    ``core.gba_shard_map.make_gba_fused_psum_step``: per-worker
    error-feedback residual (and onebit momentum) rows of shape
    ``(M, padded_total)``, row ``w`` = worker ``w``'s state — split over
    ``axis`` on the worker axis, columns local (``P(axis, None)``).
    Returns one spec per ``layout.wire_state_shapes`` entry ({} for
    ``scheme="none"``).  Reuses :func:`flat_slice_specs`'s geometry
    validation so a stale layout fails at spec-build time."""
    flat_slice_specs(layout, mesh, axis)        # geometry validation only
    m = _axis_size(mesh, axis)
    return {name: P(axis, None)
            for name in layout.wire_state_shapes(m, scheme)}


def fused_state_specs(layout: Any, mesh: Mesh, pspecs: Any,
                      axis: str = "data") -> dict:
    """Spec tree for ``launch.programs``'s fused train state: model params
    keep their per-leaf rules (``pspecs``, the forward consumes them),
    while the Adagrad accumulator and the M-slot gradient buffer live
    flat — sliced over ``axis`` for a ShardedFlatLayout, replicated for
    the single-host ``FlatLayout``."""
    from repro.core.flat_sharded import ShardedFlatLayout
    if isinstance(layout, ShardedFlatLayout):
        flat = flat_slice_specs(layout, mesh, axis)
    else:
        flat = {"flat": P(), "buffer": {"grads": P(), "tokens": P(),
                                        "fill": P(), "step": P()}}
    return {"params": pspecs, "accum": flat["flat"],
            "buffer": flat["buffer"]}


def cache_specs(cache_shapes: Any, cfg: ModelConfig, mesh: Mesh,
                batch: int) -> Any:
    """Decode-cache PartitionSpecs.  Batch shards over (pod, data) when it
    divides; otherwise (long_500k, B=1) the KV sequence dim shards over
    ``data`` — sequence-parallel cache."""
    dp = data_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= _axis_size(mesh, a)
    batch_ok = batch % dp_size == 0
    bspec = dp if batch_ok else None
    seq_axis = None if batch_ok else "data"

    def per_leaf(path, leaf):
        names = _path_names(path)
        name = names[-1]
        stacked = 1 if "blocks" in names else 0
        lead = (None,) * stacked
        core = leaf.shape[stacked:]
        if name in ("k", "v"):
            b, L, kv, hd = core
            kvs = _maybe(kv, mesh, "model")
            hds = None if kvs else _maybe(hd, mesh, "model")
            Ls = seq_axis if (seq_axis and _fits(L, mesh, "data")) else None
            return P(*lead, bspec, Ls, kvs, hds)
        if name == "ssm":
            b, h, pdim, n = core
            return P(*lead, bspec, _maybe(h, mesh, "model"), None, None)
        if name == "conv":
            b, w, c = core
            return P(*lead, bspec, None, _maybe(c, mesh, "model"))
        if name == "memory":
            b, t, d = core
            return P(bspec, None, None)
        return P(*([None] * leaf.ndim))  # pos scalar etc.

    return jax.tree_util.tree_map_with_path(per_leaf, cache_shapes)


def batch_partition(mesh: Mesh, batch: int, ndim: int) -> P:
    dp = data_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= _axis_size(mesh, a)
    lead = dp if batch % dp_size == 0 else None
    return P(lead, *([None] * (ndim - 1)))


def to_named(specs: Any, mesh: Mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))
