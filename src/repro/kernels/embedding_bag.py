"""Pallas TPU kernels: pooled hash-embedding lookup + sorted-scatter grad,
with HBM-resident tables and double-buffered DMA block streaming.

The compute hot-spot of the paper's recommendation workloads is the sparse
module: per-batch gather of F rows per example (forward) and the per-ID
normalized scatter-add (backward, Alg. 2 line 23).  Production vocabularies
(10^6-10^8 hashed IDs) never fit a ``(V, D)`` VMEM block, so both kernels
keep the big arrays in HBM (``pl.ANY`` memory space) and stream
fixed-size blocks through a 2-deep VMEM scratch pipeline with
``pltpu.make_async_copy``: the DMA of block ``c+1`` overlaps the compute of
block ``c``, and the VMEM footprint is O(block) — independent of the
vocabulary size ``V`` and the entry count ``E = B*F``.

* forward: the B*F (id, batch_row) entries are sorted by id ONCE on the
  XLA side and bucketed into ``BLOCK_V``-row vocab blocks (searchsorted
  segment offsets — the same sort machinery the backward uses).  A
  precomputed (block, chunk) step schedule drives one fused pipeline per
  ``BLOCK_D`` output tile: each step DMAs the next ``(BLOCK_V, BLOCK_D)``
  table tile (only when the block changes — empty blocks are never
  streamed) and the window holding the next ``CHUNK_E`` entries, then
  pools the chunk into the ``(B, BLOCK_D)`` accumulator as two MXU
  matmuls: the ``(B, V_blk)`` count matrix of the chunk's (batch row, id)
  pairs, then ``counts @ tile`` — no dynamic VMEM gathers.  The D tiling
  is the forward's only parallel grid axis; vocab blocks run serially
  inside a program, hidden behind the DMA overlap.

* backward: **sort-based segment reduce** over disjoint ``(BLOCK_V,
  BLOCK_D)`` output tiles (grid = vocab blocks x D blocks, race-free,
  fully parallel).  Each program streams its contiguous run of sorted
  (id, row) entries in ``CHUNK_E``-sized chunks through the double
  buffer and reduces them as a one-hot matmul
  ``(BLOCK_V, W) @ (W, BLOCK_D)``; per-ID contributor counts (Alg. 2
  line 23) fall out of the same one-hot mask.

* row scatter (``scatter_rows``): the backward with one row an id,
  D-major in and out: the sorted rows stream as ``(D, W)``
  windows, entries on lanes as the ids travel, each folded in as
  ``rows_t @ onehot^T`` in three one-pass bfloat16 products, into the
  ``(D, V)`` gradient; a narrow table pads D to 16 sublanes, not 128
  lanes.  A trainer that differentiates by gathered rows sums a step's
  rows into the table gradient with it, under a jitted entry of its own
  name, and counts each id's contributing slots in the same pass: a
  per-entry count rides as one more row column, kept at the first entry
  of each (slot, id) pair of the sorted stream and zeroed at the rest.

TPU layout rules shape the streams.  The TPU DMAs HBM in whole 128-lane
tiles, so ids travel lane-major (``(1, E)`` / ``(2, E)`` rows) and each
chunk, which may start anywhere, moves as the lane-aligned window of
``W = _window(CHUNK_E)`` entries that holds it, masked to the chunk.  D
tiles are whole lane tiles too: Mosaic refuses a ``(BLOCK_V, 16)`` slice
of an HBM table, so a narrow table (criteo's D=16) is padded to 128 lanes
on every forward call, an O(V) copy until tables are stored lane-dense.
The one-hot matmuls run at HIGHEST precision, so the MXU moves f32 table
values exactly.

Batch rows the caller padded (and any other out-of-range id) are mapped to
a sentinel id ``>= V_pad`` that sorts past the last block boundary, so they
issue no DMA traffic at all.

``embedding_bag_grad_resident`` keeps the whole sorted arrays in VMEM and
shares the streamed kernel's windows and arithmetic: a regression oracle
for the DMA transport, which the streamed kernel reproduces bit-for-bit on
VMEM-sized configs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import runtime
from repro.kernels.launch_meta import (ANY, BlockMeta, LaunchMeta,
                                       ScratchMeta, block_specs,
                                       scratch_shapes)

BLOCK_V = 512      # vocab rows per streamed table tile / backward out block
CHUNK_E = 256      # sorted (id, row) entries consumed per pipeline step
BLOCK_D = 128      # embedding columns per output tile (wide-D streaming)
LANE = 128         # TPU lane width: HBM DMA windows are whole lane tiles
ROW_SUBLANES = 16  # the row scatter's D pad: whole bfloat16 sublane tiles

# one-hot matmuls must move table values exactly, so f32 contractions run
# at full precision on the MXU (a no-op in interpret mode)
_EXACT = jax.lax.Precision.HIGHEST


def _round_up(x: int, m: int) -> int:
    return x + (-x) % m


def _block_d(d: int, block_d: int) -> int:
    """Effective D tile: ``min(d, block_d)`` rounded up to whole 128-lane
    tiles.  Mosaic refuses HBM slices narrower than a lane tile, so a
    narrow table (criteo's D=16) is padded to one tile — in interpret mode
    too, so the CPU runs the chip's geometry."""
    return _round_up(min(d, block_d), LANE)


def lane_dense(table: jax.Array) -> jax.Array:
    """``table`` with its minor axis padded to whole lane tiles: the
    layout the kernels stream.  A caller that looks up the same table many
    times pads it once here, and the kernels then skip their per-call pad;
    the extra output columns are zero."""
    d = table.shape[1]
    return jnp.pad(table, ((0, 0), (0, _round_up(d, LANE) - d)))


def _window(chunk_e: int) -> int:
    """Entries per DMA window: a ``chunk_e`` chunk starts anywhere in the
    sorted stream, so each step moves the lane-aligned window holding it
    and masks the entries outside the chunk."""
    return _round_up(chunk_e + LANE - 1, LANE)


def stream_vmem_bytes(d: int, *, table_itemsize: int = 4,
                      row_itemsize: int = 4, block_v: int = BLOCK_V,
                      block_d: int = BLOCK_D, chunk_e: int = CHUNK_E) -> dict[str, int]:
    """Derived VMEM residency of the streamed pipelines (double-buffered
    scratch only — the V- and E-sized arrays stay in HBM).  This is the
    block-bounded footprint the bench rows record as ``vmem_bytes``."""
    bd = _block_d(d, block_d)
    w = _window(chunk_e)
    return {
        # 2 table tiles + 2 (id, batch_row) entry windows
        "fwd": 2 * block_v * bd * table_itemsize + 2 * 2 * w * 4,
        # 2 gradient-row windows + 2 id windows
        "bwd": 2 * w * bd * row_itemsize + 2 * w * 4,
        "block_d": bd,
    }


def _entry_pad(e: int, chunk_e: int) -> int:
    """Padded sorted-entry length: the DMA window of the last chunk never
    runs off the end (mirrors ``_sorted_entries``)."""
    return _round_up(e, LANE) + _window(chunk_e)


def fwd_launch_meta(b: int, f: int, v: int, d: int, table_dtype=jnp.float32,
                    *, block_v: int = BLOCK_V, block_d: int = BLOCK_D,
                    chunk_e: int = CHUNK_E) -> LaunchMeta:
    """Static launch geometry of the streamed forward: the V- and E-sized
    arrays are ANY (HBM) operands, VMEM holds only the double-buffered
    tile/entry scratch plus the (B, BLOCK_D) output tile.  The kernel
    builds its specs and VMEM scratch from this meta."""
    bd = _block_d(d, block_d)
    d_pad = _round_up(d, bd)
    v_rows = max(v, block_v)
    e_pad = _entry_pad(b * f, chunk_e)
    w = _window(chunk_e)
    bp = _round_up(b, 8)
    vm = stream_vmem_bytes(d, table_itemsize=jnp.dtype(table_dtype).itemsize,
                           block_v=block_v, block_d=block_d, chunk_e=chunk_e)
    return LaunchMeta(
        kernel="embedding_bag_fwd",
        grid=(d_pad // bd,),
        num_scalar_prefetch=4,
        inputs=(
            BlockMeta("entries", (2, e_pad), jnp.int32, memory_space=ANY),
            BlockMeta("table", (v_rows, d_pad), table_dtype,
                      memory_space=ANY),
        ),
        outputs=(
            BlockMeta("out", (bp, d_pad), table_dtype, (bp, bd),
                      lambda j, *_: (0, j)),
        ),
        scratch=(
            ScratchMeta("tile_buf", (2, block_v, bd), table_dtype),
            ScratchMeta("ent_buf", (2, 2, w), jnp.int32),
        ),
        declared_vmem_bytes=vm["fwd"],
        vmem_counted=("tile_buf", "ent_buf"),
    )


def bwd_launch_meta(b: int, f: int, v: int, d: int, row_dtype=jnp.float32,
                    *, block_v: int = BLOCK_V, block_d: int = BLOCK_D,
                    chunk_e: int = CHUNK_E) -> LaunchMeta:
    """Static launch geometry of the sorted-scatter backward: grid =
    (vocab blocks x D blocks), each program owns one disjoint
    (BLOCK_V, BLOCK_D) output tile and streams its sorted run through the
    double-buffered window scratch.  Counts come out lane-major, one
    ``(1, BLOCK_V)`` row per vocab block."""
    bd = _block_d(d, block_d)
    d_pad = _round_up(d, bd)
    cap_pad = _round_up(v, block_v)
    e_pad = _entry_pad(b * f, chunk_e)
    w = _window(chunk_e)
    vm = stream_vmem_bytes(d, row_itemsize=jnp.dtype(row_dtype).itemsize,
                           block_v=block_v, block_d=block_d, chunk_e=chunk_e)
    return LaunchMeta(
        kernel="embedding_bag_bwd",
        grid=(cap_pad // block_v, d_pad // bd),
        num_scalar_prefetch=1,
        inputs=(
            BlockMeta("sorted_ids", (1, e_pad), jnp.int32, memory_space=ANY),
            BlockMeta("sorted_rows", (e_pad, d_pad), row_dtype,
                      memory_space=ANY),
        ),
        outputs=(
            BlockMeta("gtable", (cap_pad, d_pad), jnp.float32,
                      (block_v, bd), lambda i, j, *_: (i, j)),
            BlockMeta("counts", (cap_pad // block_v, 1, block_v),
                      jnp.float32, (1, 1, block_v),
                      lambda i, j, *_: (i, 0, 0)),
        ),
        scratch=(
            ScratchMeta("ids_buf", (2, 1, w), jnp.int32),
            ScratchMeta("rows_buf", (2, w, bd), row_dtype),
        ),
        declared_vmem_bytes=vm["bwd"],
        vmem_counted=("ids_buf", "rows_buf"),
    )


def scatter_rows_launch_meta(e: int, v: int, d: int, *,
                             block_v: int = BLOCK_V,
                             chunk_e: int = CHUNK_E) -> LaunchMeta:
    """Static launch geometry of the row scatter: one program per vocab
    block, streaming its sorted run of ids and D-major rows
    (``(D_pad, E_pad)``, D on sublanes, the entries on lanes as the ids
    travel) and writing its ``(D_pad, BLOCK_V)`` block of the D-major
    table gradient.  D pads to whole bfloat16 sublane tiles only."""
    d_pad = _round_up(d, ROW_SUBLANES)
    cap_pad = _round_up(v, block_v)
    e_pad = _entry_pad(e, chunk_e)
    w = _window(chunk_e)
    vmem = 2 * w * 4 + 2 * d_pad * w * 4
    return LaunchMeta(
        kernel="embedding_bag_scatter_rows",
        grid=(cap_pad // block_v,),
        num_scalar_prefetch=1,
        inputs=(
            BlockMeta("sorted_ids", (1, e_pad), jnp.int32, memory_space=ANY),
            BlockMeta("sorted_rows_t", (d_pad, e_pad), jnp.float32,
                      memory_space=ANY),
        ),
        outputs=(
            BlockMeta("gtable_t", (d_pad, cap_pad), jnp.float32,
                      (d_pad, block_v), lambda i, *_: (0, i)),
        ),
        scratch=(
            ScratchMeta("ids_buf", (2, 1, w), jnp.int32),
            ScratchMeta("rows_buf", (2, d_pad, w), jnp.float32),
        ),
        declared_vmem_bytes=vmem,
        vmem_counted=("ids_buf", "rows_buf"),
    )


# ---------------------------------------------------------------------------
# shared XLA-side sort machinery
# ---------------------------------------------------------------------------

def _sorted_entries(ids: jax.Array, capacity: int, block_v: int,
                    chunk_e: int):
    """Bucket the B*F flat ids into ``block_v``-row sorted runs.

    Returns ``(sorted_ids, order, offsets, cap_pad, nvb)``: ids sorted and
    padded so every chunk's DMA window stays in bounds, the argsort
    permutation (for gathering per-entry payloads), and per-block run
    boundaries.  Out-of-range ids — including any batch padding the caller
    added — map to the sentinel ``cap_pad``, which sorts past the last
    block boundary: no run contains them, no DMA ever moves their payload.
    """
    e = ids.size
    flat = ids.reshape(-1).astype(jnp.int32)
    cap_pad = _round_up(capacity, block_v)
    flat = jnp.where((flat >= 0) & (flat < capacity), flat, cap_pad)
    # the sort carries the permutation along, so the sorted ids need no
    # gather of their own (argsort's stable order)
    sorted_ids, order = jax.lax.sort(
        (flat, jnp.arange(e, dtype=jnp.int32)), num_keys=1, is_stable=True)
    nvb = cap_pad // block_v
    boundaries = jnp.arange(nvb + 1, dtype=jnp.int32) * block_v
    offsets = jnp.searchsorted(sorted_ids, boundaries).astype(jnp.int32)
    sorted_ids = jnp.pad(sorted_ids, (0, _entry_pad(e, chunk_e) - e),
                         constant_values=cap_pad)
    return sorted_ids, order, offsets, cap_pad, nvb


def _chunk_window(p0, end, chunk_e: int, w: int):
    """``(start, mask)`` of the lane-aligned window holding the chunk
    ``[p0, min(p0 + chunk_e, end))``: ``mask`` is ``(1, w)``, true on the
    window lanes that belong to the chunk."""
    start = pl.multiple_of(p0 - p0 % LANE, LANE)
    pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
    return start, (pos >= p0) & (pos < jnp.minimum(p0 + chunk_e, end))


def _cols(ref, j, bd: int):
    """Column window of D tile ``j``: the whole minor axis when one tile
    covers it, else a tile-aligned slice."""
    if bd == ref.shape[-1]:
        return slice(None)
    return pl.ds(pl.multiple_of(j * bd, bd), bd)


# ---------------------------------------------------------------------------
# forward: streamed pooled lookup
# ---------------------------------------------------------------------------

def _fwd_kernel(nsteps_ref, offsets_ref, sblk_ref, sp0_ref,
                entries_hbm, table_hbm, out_ref,
                tile_buf, ent_buf, tile_sem, ent_sem, *,
                block_v: int, chunk_e: int):
    """One fused (tile-DMA | entry-DMA | pool) pipeline per D tile.

    nsteps_ref:  (1,) SMEM       — live steps in the schedule
    offsets_ref: (nvb+1,) SMEM   — sorted-run boundaries per vocab block
    sblk_ref:    (S,) SMEM       — vocab block of each pipeline step
    sp0_ref:     (S,) SMEM       — absolute entry offset of each step
    entries_hbm: (2, E_pad) HBM  — row 0 sorted ids, row 1 batch rows
    table_hbm:   (V_pad, D_pad) HBM
    out_ref:     (B_pad, BLOCK_D) VMEM output tile
    tile_buf:    (2, BLOCK_V, BLOCK_D) VMEM — double-buffered table tiles
    ent_buf:     (2, 2, W) VMEM             — double-buffered entry windows

    Each step pools its chunk as ``C @ tile`` where ``C[b, v]`` counts the
    chunk's entries of batch row ``b`` and tile-local id ``v`` (an NT
    matmul of the two one-hot masks), so every operand stays lane-major.
    """
    j = pl.program_id(0)
    n = nsteps_ref[0]
    bp, bd = out_ref.shape
    v_rows = table_hbm.shape[0]
    w = ent_buf.shape[-1]
    cols = _cols(table_hbm, j, bd)

    def tile_start(blk):
        # the last block's tile is clamped instead of padding the table:
        # its run only holds ids in [blk*block_v, v), all >= the clamped
        # start, so the local one-hot still matches exactly
        return jnp.minimum(blk * block_v, v_rows - block_v)

    def tile_dma(slot, blk):
        return pltpu.make_async_copy(
            table_hbm.at[pl.ds(tile_start(blk), block_v), cols],
            tile_buf.at[slot], tile_sem.at[slot])

    def ent_dma(slot, s):
        p0 = sp0_ref[s]
        start = pl.multiple_of(p0 - p0 % LANE, LANE)
        return pltpu.make_async_copy(
            entries_hbm.at[:, pl.ds(start, w)],
            ent_buf.at[slot], ent_sem.at[slot])

    @pl.when(n > 0)
    def _():
        tile_dma(0, sblk_ref[0]).start()
        ent_dma(0, 0).start()

    vids = jax.lax.broadcasted_iota(jnp.int32, (block_v, w), 0)
    brows = jax.lax.broadcasted_iota(jnp.int32, (bp, w), 0)

    def body(s, carry):
        acc, tslot, prev_blk = carry
        blk = sblk_ref[s]
        end = offsets_ref[blk + 1]
        load = blk != prev_blk
        tslot = jnp.where(load, 1 - tslot, tslot)

        # prefetch step s+1 while step s computes: the entry window always,
        # the table tile only when s+1 crosses into a new vocab block
        @pl.when(s + 1 < n)
        def _():
            ent_dma((s + 1) % 2, s + 1).start()

            @pl.when(sblk_ref[s + 1] != blk)
            def _():
                tile_dma(1 - tslot, sblk_ref[s + 1]).start()

        @pl.when(load)
        def _():
            tile_dma(tslot, blk).wait()
        ent_dma(s % 2, s).wait()

        _, valid = _chunk_window(sp0_ref[s], end, chunk_e, w)
        ent = ent_buf[s % 2]                               # (2, W)
        idx = ent[0:1, :] - tile_start(blk)                # tile-local ids
        onehot_v = ((idx == vids) & valid).astype(jnp.float32)   # (V_blk, W)
        onehot_b = ((ent[1:2, :] == brows) & valid).astype(jnp.float32)
        counts = jax.lax.dot_general(                      # (B, V_blk)
            onehot_b, onehot_v, (((1,), (1,)), ((), ())), precision=_EXACT,
            preferred_element_type=jnp.float32)
        acc = acc + jax.lax.dot_general(                   # (B, D_blk)
            counts, tile_buf[tslot].astype(jnp.float32),
            (((1,), (0,)), ((), ())), precision=_EXACT,
            preferred_element_type=jnp.float32)
        return acc, tslot, blk

    acc, _, _ = jax.lax.fori_loop(
        0, n, body,
        (jnp.zeros((bp, bd), jnp.float32), jnp.int32(1), jnp.int32(-1)))
    out_ref[...] = acc.astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_v", "block_d", "chunk_e", "interpret"))
def _embedding_bag_streamed(ids: jax.Array, table: jax.Array, *,
                            block_v: int, block_d: int, chunk_e: int,
                            interpret: bool) -> jax.Array:
    b, f = ids.shape
    v, d = table.shape
    bd = _block_d(d, block_d)
    d_pad = _round_up(d, bd)
    # tables keep their HBM layout: the last tile's DMA start is clamped in
    # the kernel, so padding is only needed for sub-block tables (rows) and
    # D that is not a whole number of tiles (cols)
    row_pad = block_v - v if v < block_v else 0
    if row_pad or d_pad != d:
        table = jnp.pad(table, ((0, row_pad), (0, d_pad - d)))

    e = b * f
    sorted_ids, order, offsets, _, nvb = _sorted_entries(
        ids, v, block_v, chunk_e)
    e_pad = sorted_ids.shape[0]
    entries = jnp.stack([
        sorted_ids,
        jnp.pad((order // f).astype(jnp.int32), (0, e_pad - e))
    ])                                                    # (2, E_pad)

    # (block, chunk) step schedule: empty blocks contribute no steps, so
    # only tiles with at least one id are ever streamed
    lens = offsets[1:] - offsets[:-1]
    nchunks = (lens + chunk_e - 1) // chunk_e             # per block
    s_max = nvb + e // chunk_e              # sum(nchunks) can't exceed this
    n_steps = jnp.sum(nchunks).astype(jnp.int32)
    first_step = jnp.cumsum(nchunks) - nchunks
    step_blk = jnp.repeat(jnp.arange(nvb, dtype=jnp.int32), nchunks,
                          total_repeat_length=s_max)
    chunk_in_blk = jnp.arange(s_max, dtype=jnp.int32) - first_step[step_blk]
    step_p0 = offsets[step_blk] + chunk_in_blk * chunk_e

    bp = _round_up(b, 8)
    meta = fwd_launch_meta(b, f, v, d, table.dtype, block_v=block_v,
                           block_d=block_d, chunk_e=chunk_e)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, block_v=block_v, chunk_e=chunk_e),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=meta.num_scalar_prefetch,
            grid=meta.grid,
            in_specs=block_specs(meta.inputs),
            out_specs=block_specs(meta.outputs)[0],
            scratch_shapes=scratch_shapes(meta.scratch) + [
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((bp, d_pad), table.dtype),
        interpret=interpret,
    )(jnp.reshape(n_steps, (1,)), offsets, step_blk, step_p0,
      entries, table)
    return out[:b, :d]


def embedding_bag(ids: jax.Array, table: jax.Array, *,
                  block_v: int | None = None, block_d: int | None = None,
                  chunk_e: int | None = None,
                  interpret: bool | None = None) -> jax.Array:
    """ids: (B, F) int32, table: (V, D) -> pooled (B, D).

    The table stays in HBM; VMEM holds 2 ``(block_v, block_d)`` tiles and
    2 entry windows regardless of V (module docstring)."""
    return _embedding_bag_streamed(
        ids, table, block_v=block_v or BLOCK_V, block_d=block_d or BLOCK_D,
        chunk_e=chunk_e or CHUNK_E, interpret=runtime.resolve(interpret))


# ---------------------------------------------------------------------------
# backward: streamed sorted-scatter segment reduce
# ---------------------------------------------------------------------------

def _sorted_grad_rows(ids: jax.Array, grad_out: jax.Array, capacity: int,
                      block_v: int, chunk_e: int, d_pad: int):
    """Sorted-run bucketing (shared ``_sorted_entries``) plus the per-entry
    gradient-row payload, D-padded for tiling and length-padded to match
    the sentinel-padded id stream."""
    f = ids.shape[1]
    sorted_ids, order, offsets, cap_pad, nvb = _sorted_entries(
        ids, capacity, block_v, chunk_e)
    rows = grad_out[order // f]                           # (E, D)
    if d_pad != grad_out.shape[1]:
        rows = jnp.pad(rows, ((0, 0), (0, d_pad - grad_out.shape[1])))
    rows = jnp.pad(rows, ((0, sorted_ids.shape[0] - rows.shape[0]), (0, 0)))
    return sorted_ids, rows, offsets, cap_pad, nvb


def _reduce_window(acc, cnt, ids, rows, valid, vids):
    """Fold one window into the ``(BLOCK_V, D)`` segment sum and the
    ``(1, BLOCK_V)`` contributor counts, as one-hot matmuls.

    ids: (1, W) sorted ids; rows: (W, D) their gradient rows; valid: (1, W)
    chunk mask; vids: (BLOCK_V, W) the output tile's global ids."""
    onehot = ((ids == vids) & valid).astype(jnp.float32)       # (V, W)
    acc = acc + jax.lax.dot_general(
        onehot, rows.astype(jnp.float32), (((1,), (0,)), ((), ())),
        precision=_EXACT, preferred_element_type=jnp.float32)  # (V, D)
    cnt = cnt + jax.lax.dot_general(
        jnp.ones_like(valid, jnp.float32), onehot, (((1,), (1,)), ((), ())),
        precision=_EXACT, preferred_element_type=jnp.float32)  # (1, V)
    return acc, cnt


def _stream_run(offsets_ref, ids_hbm, ids_buf, ids_sem, rows_window,
                rows_buf, rows_sem, fold, init, *, block_v: int,
                chunk_e: int):
    """Fold this program's sorted run (vocab block ``i``) into ``init``,
    window by window: ``fold(carry, ids, rows, valid, vids)``.

    offsets_ref: (nvb+1,) SMEM — run boundaries in the sorted arrays
    ids_hbm:     (1, E_pad) HBM — sorted ids
    rows_window: ``(w0, w)`` -> the HBM slice of the rows of sorted
                 entries ``[w0, w0 + w)``
    ids_buf:     (2, 1, W) / rows_buf: (2, ...) — double-buffered window
                 pipeline
    """
    i = pl.program_id(0)
    start = offsets_ref[i]
    end = offsets_ref[i + 1]
    w = ids_buf.shape[-1]
    nchunks = (end - start + chunk_e - 1) // chunk_e

    def dmas(slot, c):
        w0, _ = _chunk_window(start + c * chunk_e, end, chunk_e, w)
        return (
            pltpu.make_async_copy(ids_hbm.at[:, pl.ds(w0, w)],
                                  ids_buf.at[slot], ids_sem.at[slot]),
            pltpu.make_async_copy(rows_window(w0, w),
                                  rows_buf.at[slot], rows_sem.at[slot]))

    @pl.when(nchunks > 0)
    def _():
        for dma in dmas(0, 0):
            dma.start()

    vids = i * block_v + jax.lax.broadcasted_iota(jnp.int32, (block_v, w), 0)

    def body(c, carry):
        cur = c % 2

        @pl.when(c + 1 < nchunks)
        def _():
            for dma in dmas((c + 1) % 2, c + 1):   # overlap chunk c compute
                dma.start()

        for dma in dmas(cur, c):
            dma.wait()
        _, valid = _chunk_window(start + c * chunk_e, end, chunk_e, w)
        return fold(carry, ids_buf[cur], rows_buf[cur], valid, vids)

    return jax.lax.fori_loop(0, nchunks, body, init)


def _bwd_kernel(offsets_ref, ids_hbm, rows_hbm, gtable_ref, counts_ref,
                ids_buf, rows_buf, ids_sem, rows_sem, *,
                block_v: int, chunk_e: int):
    """Segment reduce for one (vocab block, D block) output tile.

    rows_hbm:    (E_pad, D_pad) HBM — gradient rows in sorted-id order
    gtable_ref:  (BLOCK_V, BLOCK_D) VMEM output tile owned by this program
    counts_ref:  (1, 1, BLOCK_V) contributor counts (recomputed per D
                 block — every D block of a vocab block derives the same)
    rows_buf:    (2, W, BLOCK_D)
    """
    bd = gtable_ref.shape[1]
    cols = _cols(rows_hbm, pl.program_id(1), bd)
    acc, cnt = _stream_run(
        offsets_ref, ids_hbm, ids_buf, ids_sem,
        lambda w0, w: rows_hbm.at[pl.ds(w0, w), cols], rows_buf, rows_sem,
        lambda carry, *window: _reduce_window(*carry, *window),
        (jnp.zeros((block_v, bd), jnp.float32),
         jnp.zeros((1, block_v), jnp.float32)),
        block_v=block_v, chunk_e=chunk_e)
    gtable_ref[...] = acc
    counts_ref[0] = cnt


def _scatter_rows_kernel(offsets_ref, ids_hbm, rows_t_hbm, gtable_t_ref,
                         ids_buf, rows_buf, ids_sem, rows_sem, *,
                         block_v: int, chunk_e: int):
    """The row scatter for one vocab block, D-major in and out.

    rows_t_hbm:   (D_pad, E_pad) HBM — the rows in sorted-id order, one
                  lane per entry
    gtable_t_ref: (D_pad, BLOCK_V) VMEM block of the (D_pad, V) gradient
    rows_buf:     (2, D_pad, W)

    A window folds in as ``rows_t @ onehot^T``.  A float32 row is the sum
    of three bfloat16 parts, and a 0/1 one-hot times a bfloat16 part is
    exact, so three one-pass products with float32 sums move the rows
    exactly."""

    def fold(acc, ids, rows_t, valid, vids):
        onehot = ((ids == vids) & valid).astype(jnp.bfloat16)  # (V, W)
        rest = rows_t
        for _ in range(3):
            part = rest.astype(jnp.bfloat16)
            acc = acc + jax.lax.dot_general(
                part, onehot, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32)            # (D, V)
            rest = rest - part.astype(jnp.float32)
        return acc

    gtable_t_ref[...] = _stream_run(
        offsets_ref, ids_hbm, ids_buf, ids_sem,
        lambda w0, w: rows_t_hbm.at[:, pl.ds(w0, w)], rows_buf, rows_sem,
        fold, jnp.zeros(gtable_t_ref.shape, jnp.float32),
        block_v=block_v, chunk_e=chunk_e)


# A compiled program names each kernel call after the jitted entry that
# made it, so the pooled backward and the row scatter stand apart in a
# trace.
@functools.partial(
    jax.jit,
    static_argnames=("capacity", "block_v", "block_d", "chunk_e",
                     "interpret"))
def _embedding_bag_grad_streamed(ids: jax.Array, grad_out: jax.Array,
                                 capacity: int, *, block_v: int,
                                 block_d: int, chunk_e: int, interpret: bool
                                 ) -> tuple[jax.Array, jax.Array]:
    d = grad_out.shape[1]
    bd = _block_d(d, block_d)
    d_pad = _round_up(d, bd)
    sorted_ids, sorted_rows, offsets, cap_pad, nvb = _sorted_grad_rows(
        ids, grad_out, capacity, block_v, chunk_e, d_pad)

    meta = bwd_launch_meta(ids.shape[0], ids.shape[1], capacity, d,
                           grad_out.dtype, block_v=block_v,
                           block_d=block_d, chunk_e=chunk_e)
    gtable, counts = pl.pallas_call(
        functools.partial(_bwd_kernel, block_v=block_v, chunk_e=chunk_e),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=meta.num_scalar_prefetch,
            grid=meta.grid,
            in_specs=block_specs(meta.inputs),
            out_specs=block_specs(meta.outputs),
            scratch_shapes=scratch_shapes(meta.scratch) + [
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((cap_pad, d_pad), jnp.float32),
            jax.ShapeDtypeStruct((nvb, 1, block_v), jnp.float32),
        ],
        interpret=interpret,
    )(offsets, sorted_ids.reshape(1, -1), sorted_rows)
    return gtable[:capacity, :d], counts.reshape(-1)[:capacity]


@functools.partial(
    jax.jit, static_argnames=("capacity", "block_v", "chunk_e", "interpret"))
def _scatter_rows_streamed(ids: jax.Array, rows: jax.Array, capacity: int,
                           counts: jax.Array | None = None, *, block_v: int,
                           chunk_e: int, interpret: bool):
    d = rows.shape[-1]
    e = ids.size
    sorted_ids, order, offsets, cap_pad, nvb = _sorted_entries(
        ids, capacity, block_v, chunk_e)
    if counts is not None:
        # the counts ride as one more row column, in the same gather
        rows = jnp.concatenate(
            [rows.astype(jnp.float32),
             counts.astype(jnp.float32).reshape(ids.shape + (1,))], axis=-1)
    # gathered where they lie, with no flattening copy, and turned
    # D-major: the entries on lanes, as the ids travel
    rows_t = rows[jnp.unravel_index(order, ids.shape)].astype(jnp.float32).T
    if counts is not None:
        # one count a distinct (leading index, id) pair: the sort is
        # stable, so a pair's entries lie side by side, and all but the
        # first are zeroed
        lead = order // (e // ids.shape[0])
        s = sorted_ids[:e]
        first = jnp.concatenate([jnp.ones((1,), bool),
                                 (s[1:] != s[:-1]) | (lead[1:] != lead[:-1])])
        rows_t = rows_t.at[d].set(jnp.where(first, rows_t[d], 0.0))
    meta = scatter_rows_launch_meta(e, capacity, rows_t.shape[0],
                                    block_v=block_v, chunk_e=chunk_e)
    d_pad = meta.inputs[1].array_shape[0]
    rows_t = jnp.pad(rows_t, ((0, d_pad - rows_t.shape[0]),
                              (0, sorted_ids.shape[0] - rows_t.shape[1])))
    (gtable_t,) = pl.pallas_call(
        functools.partial(_scatter_rows_kernel, block_v=block_v,
                          chunk_e=chunk_e),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=meta.num_scalar_prefetch,
            grid=meta.grid,
            in_specs=block_specs(meta.inputs),
            out_specs=block_specs(meta.outputs),
            scratch_shapes=scratch_shapes(meta.scratch) + [
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(o.array_shape, o.dtype)
                   for o in meta.outputs],
        interpret=interpret,
    )(offsets, sorted_ids.reshape(1, -1), rows_t)
    table = gtable_t[:d, :capacity].T
    if counts is None:
        return table
    return table, gtable_t[d, :capacity]


def scatter_rows(ids: jax.Array, rows: jax.Array, capacity: int,
                 counts: jax.Array | None = None, *,
                 block_v: int | None = None, chunk_e: int | None = None,
                 interpret: bool | None = None):
    """Sum rows into a table: ids (...), rows ``ids.shape + (D,)`` ->
    (capacity, D), each row the sum of the rows whose id it is.

    With ``counts`` (shaped like ``ids``), returns ``(table, per_id)``:
    ``per_id[v]`` sums ``counts`` over the distinct (leading index of
    ``ids``, v) pairs, one entry a pair, so a pair's ``counts`` must be
    the same at all its entries.  The counts are summed exactly as one
    more row column.

    The backward's sort and streamed segment reduce with one row an id,
    D-major throughout.  Out-of-range ids are dropped."""
    return _scatter_rows_streamed(
        ids, rows, capacity, counts, block_v=block_v or BLOCK_V,
        chunk_e=chunk_e or CHUNK_E, interpret=runtime.resolve(interpret))


def embedding_bag_grad(ids: jax.Array, grad_out: jax.Array, capacity: int,
                       *, block_v: int | None = None,
                       block_d: int | None = None,
                       chunk_e: int | None = None,
                       interpret: bool | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """Scatter grads back to rows with per-ID contributor counts.

    ids: (B, F); grad_out: (B, D) -> (grad_table (V, D), counts (V,)).

    Sort once, then stream disjoint segments through the double-buffered
    window pipeline in parallel over (vocab block x D block) — see the
    module docstring for the design."""
    return _embedding_bag_grad_streamed(
        ids, grad_out, capacity, block_v=block_v or BLOCK_V,
        block_d=block_d or BLOCK_D, chunk_e=chunk_e or CHUNK_E,
        interpret=runtime.resolve(interpret))


# ---------------------------------------------------------------------------
# PR-1 VMEM-resident backward — kept as a bit-exactness regression oracle
# ---------------------------------------------------------------------------

def _bwd_kernel_resident(offsets_ref, ids_ref, rows_ref, gtable_ref,
                         counts_ref):
    """Resident segment reduce: the whole sorted ``(E_pad, D)`` array sits in
    VMEM via a full-array BlockSpec (only viable for VMEM-sized configs).
    Same windows and arithmetic as ``_bwd_kernel``; only the transport
    differs."""
    i = pl.program_id(0)
    start = offsets_ref[i]
    end = offsets_ref[i + 1]
    w = _window(CHUNK_E)
    vids = i * BLOCK_V + jax.lax.broadcasted_iota(jnp.int32, (BLOCK_V, w), 0)

    def body(c, carry):
        w0, valid = _chunk_window(start + c * CHUNK_E, end, CHUNK_E, w)
        return _reduce_window(*carry, ids_ref[:, pl.ds(w0, w)],
                              rows_ref[pl.ds(w0, w), :], valid, vids)

    nchunks = (end - start + CHUNK_E - 1) // CHUNK_E
    acc, cnt = jax.lax.fori_loop(
        0, nchunks, body,
        (jnp.zeros((BLOCK_V, rows_ref.shape[1]), jnp.float32),
         jnp.zeros((1, BLOCK_V), jnp.float32)))
    gtable_ref[...] = acc
    counts_ref[0] = cnt


@functools.partial(jax.jit, static_argnames=("capacity", "interpret"))
def _embedding_bag_grad_resident(ids: jax.Array, grad_out: jax.Array,
                                 capacity: int, *, interpret: bool
                                 ) -> tuple[jax.Array, jax.Array]:
    d = grad_out.shape[1]
    d_pad = _round_up(d, LANE)              # the streamed kernel's rows
    sorted_ids, sorted_rows, offsets, cap_pad, nvb = _sorted_grad_rows(
        ids, grad_out, capacity, BLOCK_V, CHUNK_E, d_pad)
    e_pad = sorted_ids.shape[0]

    gtable, counts = pl.pallas_call(
        _bwd_kernel_resident,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nvb,),
            in_specs=[
                pl.BlockSpec((1, e_pad), lambda i, *_: (0, 0)),
                pl.BlockSpec((e_pad, d_pad), lambda i, *_: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((BLOCK_V, d_pad), lambda i, *_: (i, 0)),
                pl.BlockSpec((1, 1, BLOCK_V), lambda i, *_: (i, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((cap_pad, d_pad), jnp.float32),
            jax.ShapeDtypeStruct((nvb, 1, BLOCK_V), jnp.float32),
        ],
        interpret=interpret,
    )(offsets, sorted_ids.reshape(1, -1), sorted_rows)
    return gtable[:capacity, :d], counts.reshape(-1)[:capacity]


def embedding_bag_grad_resident(ids: jax.Array, grad_out: jax.Array,
                                capacity: int, *,
                                interpret: bool | None = None
                                ) -> tuple[jax.Array, jax.Array]:
    return _embedding_bag_grad_resident(
        ids, grad_out, capacity, interpret=runtime.resolve(interpret))
