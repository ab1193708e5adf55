"""LRU hot-ID cache in front of the HBM-resident embedding tables.

Production recsys traffic is Zipf-skewed: a few thousand hot IDs cover
most lookups.  The DMA-streamed ``pooled_lookup`` kernel already makes
the cold path cheap (O(block) VMEM at any capacity); this cache makes the
hot path FREE — a batch whose unique IDs all hit is served from host
memory without invoking the streamed kernel at all (provable via the
``repro.kernels.ops.kernel_calls`` counter; the serving bench gates it as
``audit_hit_skips_kernel``).

Consistency with live param sync
--------------------------------
Every cached row is stamped with the snapshot version it was fetched
under.  On each sync the owner calls :meth:`bump_version` with the rows
the update touched: touched entries are dropped (they would be stale),
untouched entries survive (their table rows are bit-identical in the new
snapshot, so serving them stays bit-exact).  ``touched_ids=None`` means
"unknown what changed" and clears everything.  A ``put_many`` carrying a
version other than the cache's current one is IGNORED — the harmless
outcome of the benign race where a sync lands between a miss-fetch and
its insertion.

Bit-exactness of the cached read path
-------------------------------------
:func:`cached_pooled_lookup` always pools in float32 numpy over
*per-unique-ID rows*: hits come from the cache, misses are fetched
through the streamed kernel as pools-of-one (ids shaped ``(n, 1)`` — a
sum-pool over one element IS the row).  Hit or miss, the row values are
identical to the table's rows, and the pooling order is fixed by the
request (``rows[inverse].sum(axis=1)``), so ANY hit/miss mix produces
bit-identical pooled outputs — the property the live-vs-fresh serving
acceptance test pins.
"""
from __future__ import annotations

import threading
from collections import OrderedDict

import jax.numpy as jnp
import numpy as np

from repro.embeddings.table import EmbeddingTable, StreamConfig
from repro.kernels import ops


def _pad_pow2(n: int, floor: int = 8) -> int:
    """Pad miss-batch sizes to a power of two (>= floor) so the jitted
    streamed kernel sees a bounded set of shapes instead of retracing on
    every distinct miss count."""
    p = floor
    while p < n:
        p *= 2
    return p


class HotIDCache:
    """Thread-safe LRU of (hashed id -> f32 row) with version stamping.

    ``capacity`` is the max resident rows; ``dim`` the row width.  Reads
    and writes take a short lock around dict ops only — never around a
    kernel call."""

    def __init__(self, capacity: int, dim: int):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.dim = int(dim)
        self.version = 1
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.invalidations = 0
        self._lock = threading.Lock()
        self._rows: OrderedDict[int, np.ndarray] = OrderedDict()

    # -- geometry (exact-gated in the serving bench) -----------------------
    @property
    def nbytes(self) -> int:
        """Worst-case resident bytes: capacity f32 rows."""
        return self.capacity * self.dim * 4

    def __len__(self) -> int:
        return len(self._rows)

    @property
    def hit_rate(self) -> float:
        with self._lock:   # hits/misses move together under the lock
            total = self.hits + self.misses
            return self.hits / total if total else 0.0

    # -- read/write --------------------------------------------------------
    def get_many(self, ids: np.ndarray
                 ) -> tuple[np.ndarray, np.ndarray]:
        """ids: (n,) unique int -> (rows (n, dim) f32, found (n,) bool).
        Rows for missing ids are zero-filled (caller overwrites them)."""
        ids = np.asarray(ids).reshape(-1)
        rows = np.zeros((ids.shape[0], self.dim), np.float32)
        found = np.zeros(ids.shape[0], bool)
        with self._lock:
            for i, raw in enumerate(ids):
                key = int(raw)
                row = self._rows.get(key)
                if row is not None:
                    self._rows.move_to_end(key)   # LRU touch
                    rows[i] = row
                    found[i] = True
            self.hits += int(found.sum())
            self.misses += int((~found).sum())
        return rows, found

    def put_many(self, ids: np.ndarray, rows: np.ndarray,
                 version: int) -> bool:
        """Insert freshly fetched rows.  Dropped (returns False) when
        ``version`` is not the cache's current version — the miss fetch
        raced a sync and its rows may be stale."""
        with self._lock:
            if int(version) != self.version:
                return False
            for raw, row in zip(np.asarray(ids).reshape(-1), rows):
                self._rows[int(raw)] = np.asarray(row, np.float32)
                self._rows.move_to_end(int(raw))
            while len(self._rows) > self.capacity:
                self._rows.popitem(last=False)
                self.evictions += 1
            return True

    # -- sync-side invalidation -------------------------------------------
    def bump_version(self, version: int,
                     touched_ids: np.ndarray | None = None) -> None:
        """Adopt a new snapshot version.  Entries for ``touched_ids`` are
        dropped; the rest stay valid (their rows did not change).  With
        ``touched_ids=None`` the whole cache is cleared."""
        with self._lock:
            if touched_ids is None:
                self.invalidations += len(self._rows)
                self._rows.clear()
            else:
                for raw in np.asarray(touched_ids).reshape(-1):
                    if self._rows.pop(int(raw), None) is not None:
                        self.invalidations += 1
            self.version = int(version)

    def clear(self) -> None:
        with self._lock:
            self._rows.clear()


def fetch_rows(table: jnp.ndarray, ids: np.ndarray, *,
               stream: StreamConfig | None = None,
               dim: int | None = None) -> np.ndarray:
    """Fetch exact table rows through the DMA-streamed kernel: ids are
    shaped (n_pad, 1) so each output is a sum-pool over ONE element —
    i.e. the row itself.  The batch is padded to a power of two with the
    out-of-range sentinel id ``capacity`` (the kernel maps it to the
    no-DMA sentinel slot → zero row, sliced off here), bounding the set
    of shapes the jitted kernel ever traces.  ``dim`` keeps the first
    ``dim`` columns of a lane-dense table (``embedding_bag.lane_dense``)."""
    ids = np.asarray(ids).reshape(-1)
    n = ids.shape[0]
    s = stream or StreamConfig()
    n_pad = _pad_pow2(n)
    padded = np.full((n_pad, 1), table.shape[0], np.int32)   # sentinel
    padded[:n, 0] = ids
    rows = ops.pooled_lookup(jnp.asarray(padded), table,
                             block_v=s.block_v, block_d=s.block_d,
                             chunk_e=s.chunk_e, interpret=s.interpret)
    return np.asarray(rows, np.float32)[:n, :dim]


def cached_pooled_lookup(cache: HotIDCache | None, tbl: EmbeddingTable,
                         hashed_ids: np.ndarray, *,
                         version: int = 1,
                         stream: StreamConfig | None = None,
                         dim: int | None = None) -> np.ndarray:
    """Sum-pooled lookup (B, F) -> (B, dim) through the hot-ID cache.

    Unique hit ids are served from the cache; misses fall through to
    :func:`fetch_rows` (the streamed kernel) and are inserted under
    ``version``.  A batch with zero unique misses performs ZERO kernel
    invocations.  Output is f32 numpy, bit-identical regardless of the
    hit/miss mix (see module docstring).  ``dim`` is the logical width
    of a lane-dense ``tbl``."""
    ids = np.asarray(hashed_ids)
    B, F = ids.shape
    uniq, inv = np.unique(ids.reshape(-1), return_inverse=True)
    if cache is None:
        rows = fetch_rows(tbl.table, uniq, stream=stream, dim=dim)
    else:
        rows, found = cache.get_many(uniq)
        miss = ~found
        if miss.any():
            fetched = fetch_rows(tbl.table, uniq[miss], stream=stream,
                                 dim=dim)
            rows[miss] = fetched
            cache.put_many(uniq[miss], fetched, version)
    return rows[inv].reshape(B, F, rows.shape[-1]).sum(axis=1,
                                                       dtype=np.float32)
