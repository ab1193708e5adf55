"""Pallas TPU kernels: wire quantization for the fused-psum routing stage.

The layer-grouped fused-psum schedule (``core.gba_shard_map``) routes
each group's ``(M, group_shard)`` gradient block through an
``all_to_all``.  These kernels transform that block at the wire boundary
so the payload travels as int8 instead of f32:

``quantize_minmax``
    Bagua ``MinMaxUInt8`` idiom, per ``tile``-aligned slice of each row
    (the same tile the layout aligns shard slices to):
    ``zero_point = min``, ``scale = (max - min) / 255``, code =
    ``round((x - zp) / scale)`` in [0, 255] stored as int8 (code - 128).
``quantize_sign``
    1-bit idiom: ``sign(x)`` as int8 with a per-tile mean-|x| norm as
    the single f32 sideband word.

Both quantizers emit the **error-feedback residual**
``payload - dequantize(quantize(payload))`` in the same VMEM pass — the
payload and its dequantized image are both already in VMEM, so error
feedback costs no extra launch and no extra HBM round-trip, and the
residual is bit-exactly consistent with what ``dequantize`` reconstructs
on the receiving shard (identical arithmetic, identical sideband).

Per-tile scale/zero sidebands are ``(R, n_tiles)`` f32 arrays.  Inside
the launch they are laid out tile-major, ``(n_tiles, R, 1)``, so grid step
``i`` owns the whole ``(1, R, 1)`` block ``i`` beside its ``(R, tile)``
payload block: no step slices single lanes out of a shared block, which
the TPU compiler refuses.  The wrappers transpose to and from the
``(R, n_tiles)`` wire layout.
Every launch exports a :class:`~repro.kernels.launch_meta.LaunchMeta`
the real ``pallas_call`` builds its specs from, so the static auditor
(``repro.analysis``) checks tiles/VMEM/grid of the launch that runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import runtime
from repro.kernels.launch_meta import BlockMeta, LaunchMeta, block_specs

MODES = ("minmax", "sign")


def _check_geometry(r: int, c: int, tile: int) -> int:
    if tile < 1:
        raise ValueError(f"tile must be >= 1, got {tile}")
    if c % tile:
        raise ValueError(
            f"payload columns {c} not a multiple of tile {tile} — the "
            f"routing stage only quantizes tile-aligned group slices")
    return c // tile


def quantize_vmem_bytes(r: int, c: int, tile: int, mode: str) -> int:
    """Per-grid-step VMEM residency of a quantize launch: payload in +
    residual out f32 blocks, int8 code block, and this tile's f32
    sideband block(s) (scale, plus zero-point for minmax)."""
    _check_geometry(r, c, tile)
    sidebands = 2 if mode == "minmax" else 1
    return r * tile * 4 + r * tile * 1 + r * tile * 4 + sidebands * r * 4


def dequant_vmem_bytes(r: int, c: int, tile: int, mode: str) -> int:
    """Per-grid-step VMEM residency of a dequantize launch: int8 code
    block + f32 out block + this tile's sideband block(s)."""
    _check_geometry(r, c, tile)
    sidebands = 2 if mode == "minmax" else 1
    return r * tile * 1 + r * tile * 4 + sidebands * r * 4


def _sideband_blocks(r: int, n_tiles: int, names: tuple[str, ...]
                     ) -> tuple[BlockMeta, ...]:
    # tile-major (n_tiles, R, 1): grid step i owns block i
    return tuple(BlockMeta(name, (n_tiles, r, 1), jnp.float32, (1, r, 1),
                           lambda i: (i, 0, 0))
                 for name in names)


def quantize_launch_meta(r: int, c: int, tile: int, mode: str) -> LaunchMeta:
    """Static launch geometry of a ``(r, c)`` payload quantize; the real
    ``pallas_call`` builds its specs from this."""
    if mode not in MODES:
        raise ValueError(f"unknown quantize mode {mode!r}")
    n_tiles = _check_geometry(r, c, tile)
    sidebands = ("scale", "zero") if mode == "minmax" else ("scale",)
    return LaunchMeta(
        kernel=f"quantize_{mode}",
        grid=(n_tiles,),
        inputs=(
            BlockMeta("payload", (r, c), jnp.float32, (r, tile),
                      lambda i: (0, i)),
        ),
        outputs=(
            BlockMeta("qvals", (r, c), jnp.int8, (r, tile),
                      lambda i: (0, i)),
            *_sideband_blocks(r, n_tiles, sidebands),
            BlockMeta("residual", (r, c), jnp.float32, (r, tile),
                      lambda i: (0, i)),
        ),
        declared_vmem_bytes=quantize_vmem_bytes(r, c, tile, mode),
        vmem_counted=("payload", "qvals", *sidebands, "residual"),
    )


def dequant_launch_meta(r: int, c: int, tile: int, mode: str) -> LaunchMeta:
    """Static launch geometry of the matching dequantize."""
    if mode not in MODES:
        raise ValueError(f"unknown dequantize mode {mode!r}")
    n_tiles = _check_geometry(r, c, tile)
    sidebands = ("scale", "zero") if mode == "minmax" else ("scale",)
    return LaunchMeta(
        kernel=f"dequantize_{mode}",
        grid=(n_tiles,),
        inputs=(
            BlockMeta("qvals", (r, c), jnp.int8, (r, tile),
                      lambda i: (0, i)),
            *_sideband_blocks(r, n_tiles, sidebands),
        ),
        outputs=(
            BlockMeta("out", (r, c), jnp.float32, (r, tile),
                      lambda i: (0, i)),
        ),
        declared_vmem_bytes=dequant_vmem_bytes(r, c, tile, mode),
        vmem_counted=("qvals", *sidebands, "out"),
    )


def _minmax_kernel(pay_ref, q_ref, sc_ref, zp_ref, res_ref):
    x = pay_ref[...]                                   # (R, tile) f32
    mn = jnp.min(x, axis=1, keepdims=True)             # (R, 1)
    mx = jnp.max(x, axis=1, keepdims=True)
    scale = (mx - mn) / 255.0
    safe = jnp.where(scale > 0.0, scale, 1.0)          # constant tile -> q=0
    code = jnp.clip(jnp.round((x - mn) / safe), 0.0, 255.0)
    q = (code - 128.0).astype(jnp.int8)
    q_ref[...] = q
    sc_ref[0] = scale
    zp_ref[0] = mn
    # same expression as _dequant_minmax_kernel -> residual is consistent
    # with the receiving shard's reconstruction
    deq = (q.astype(jnp.float32) + 128.0) * scale + mn
    res_ref[...] = x - deq


def _sign_kernel(pay_ref, q_ref, sc_ref, res_ref):
    x = pay_ref[...]
    scale = jnp.mean(jnp.abs(x), axis=1, keepdims=True)
    q = jnp.where(x >= 0.0, 1, -1).astype(jnp.int8)
    q_ref[...] = q
    sc_ref[0] = scale
    deq = q.astype(jnp.float32) * scale
    res_ref[...] = x - deq


def _dequant_minmax_kernel(q_ref, sc_ref, zp_ref, out_ref):
    out_ref[...] = (q_ref[...].astype(jnp.float32) + 128.0) * sc_ref[0] \
        + zp_ref[0]


def _dequant_sign_kernel(q_ref, sc_ref, out_ref):
    out_ref[...] = q_ref[...].astype(jnp.float32) * sc_ref[0]


def _tile_major(sideband: jax.Array) -> jax.Array:
    """(R, n_tiles) wire layout -> (n_tiles, R, 1) launch layout."""
    return sideband.T[:, :, None]


def _wire(sideband: jax.Array) -> jax.Array:
    """(n_tiles, R, 1) launch layout -> (R, n_tiles) wire layout."""
    return sideband[:, :, 0].T


def quantize_minmax(payload: jax.Array, *, tile: int,
                    interpret: bool | None = None
                    ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Min-max int8 quantize with fused error feedback.

    payload: (R, C) f32, C a ``tile`` multiple ->
    ``(qvals int8 (R, C), scale f32 (R, C//tile), zero f32 (R, C//tile),
    residual f32 (R, C))`` with ``residual == payload -
    dequantize(qvals, scale, zero)`` exactly.
    """
    q, sc, zp, res = _quantize(payload, tile=tile, mode="minmax",
                               interpret=runtime.resolve(interpret))
    return q, _wire(sc), _wire(zp), res


def quantize_sign(payload: jax.Array, *, tile: int,
                  interpret: bool | None = None
                  ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Sign (1-bit) quantize with per-tile mean-|x| norm and fused error
    feedback: payload (R, C) f32 -> ``(qvals int8 ±1, scale f32
    (R, C//tile), residual f32 (R, C))``."""
    q, sc, res = _quantize(payload, tile=tile, mode="sign",
                           interpret=runtime.resolve(interpret))
    return q, _wire(sc), res


@functools.partial(jax.jit, static_argnames=("tile", "mode", "interpret"))
def _quantize(payload: jax.Array, *, tile: int, mode: str, interpret: bool):
    r, c = payload.shape
    n_tiles = _check_geometry(r, c, tile)
    meta = quantize_launch_meta(r, c, tile, mode)
    sideband = jax.ShapeDtypeStruct((n_tiles, r, 1), jnp.float32)
    return pl.pallas_call(
        _minmax_kernel if mode == "minmax" else _sign_kernel,
        grid=meta.grid,
        in_specs=block_specs(meta.inputs),
        out_specs=block_specs(meta.outputs),
        out_shape=[
            jax.ShapeDtypeStruct((r, c), jnp.int8),
            *[sideband] * (len(meta.outputs) - 2),
            jax.ShapeDtypeStruct((r, c), jnp.float32),
        ],
        name=meta.kernel,
        interpret=interpret,
    )(payload.astype(jnp.float32))


def dequantize(qvals: jax.Array, scale: jax.Array,
               zero: jax.Array | None = None, *, tile: int, mode: str,
               interpret: bool | None = None) -> jax.Array:
    """Reconstruct the f32 payload from the routed wire arrays.

    qvals: (R, C) int8; scale (and, for ``mode="minmax"``, zero):
    (R, C//tile) f32 -> (R, C) f32.
    """
    if mode == "minmax" and zero is None:
        raise ValueError("minmax dequantize needs the zero-point array")
    return _dequantize(qvals, scale, zero, tile=tile, mode=mode,
                       interpret=runtime.resolve(interpret))


@functools.partial(jax.jit, static_argnames=("tile", "mode", "interpret"))
def _dequantize(qvals, scale, zero, *, tile: int, mode: str,
                interpret: bool) -> jax.Array:
    r, c = qvals.shape
    _check_geometry(r, c, tile)
    if mode == "minmax":
        kernel, sidebands = _dequant_minmax_kernel, (scale, zero)
    elif mode == "sign":
        kernel, sidebands = _dequant_sign_kernel, (scale,)
    else:
        raise ValueError(f"unknown dequantize mode {mode!r}")
    meta = dequant_launch_meta(r, c, tile, mode)
    out, = pl.pallas_call(
        kernel,
        grid=meta.grid,
        in_specs=block_specs(meta.inputs),
        out_specs=block_specs(meta.outputs),
        out_shape=[jax.ShapeDtypeStruct((r, c), jnp.float32)],
        name=meta.kernel,
        interpret=interpret,
    )(qvals, *map(_tile_major, sidebands))
    return out
