"""Recsys scoring path: batched ID-list requests -> cached/streamed
embedding lookup -> dense tower.

This is the serving shape of the paper's workload (industrial CTR
models): a request carries ``(B, F)`` categorical ID lists; the engine
hashes them into the HBM-resident embedding table, sum-pools the rows —
through the :class:`~repro.embeddings.hot_cache.HotIDCache`, so the
Zipf-hot head of the ID distribution never touches the DMA-streamed
kernel — and scores the pooled vector with a jitted dense tower.

Live params: the engine subscribes to its :class:`ParamSource`.  On each
version swap the listener invalidates the cache entries for the rows the
update TOUCHED (the rest stay bit-valid) and adopts the new version.
Scoring pins one snapshot per call, so every score in a batch comes from
a single parameter version.

Bit-exactness: the pooled vector is produced by
:func:`~repro.embeddings.hot_cache.cached_pooled_lookup` (f32 numpy
pooling over per-unique-ID rows; see its module docstring), so a
live-synced engine and a fresh engine rebuilt from a checkpoint of the
same state return bit-identical scores — the acceptance property
``tests/test_serving_live.py`` pins at every sync boundary.
"""
from __future__ import annotations

import threading
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.embeddings.hot_cache import HotIDCache, cached_pooled_lookup
from repro.embeddings.table import EmbeddingTable, StreamConfig, hash_ids
from repro.kernels.embedding_bag import lane_dense
from repro.models.recsys import _mlp_fwd, _mlp_init
from repro.serving.config import ServingConfig
from repro.serving.sources import ParamSource, Snapshot, StaticSource


def init_scoring_params(key, capacity: int, dim: int,
                        mlp_dims: tuple[int, ...] = (64, 32)) -> dict:
    """Fresh serving params: an (capacity, dim) embedding table + a
    (dim, *mlp_dims, 1) dense tower — the pytree a GBA trainer owns and
    a checkpoint stores."""
    from repro.embeddings.table import init_table
    k1, k2 = jax.random.split(key)
    return {
        "table": init_table(k1, capacity, dim),
        "mlp": _mlp_init(k2, (dim, *mlp_dims, 1)),
    }


def _as_table(t: Any) -> EmbeddingTable:
    """Checkpoint round-trips turn the EmbeddingTable NamedTuple into a
    plain tuple — normalize back.  The table is held lane-dense, padded
    once per adopted version instead of once per miss fetch."""
    if isinstance(t, (tuple, list)):
        return EmbeddingTable(lane_dense(jnp.asarray(t[0])),
                              jnp.asarray(t[1]))
    raise TypeError(f"expected EmbeddingTable, got {type(t)!r}")


class RecsysScoringEngine:
    """Batched ID-list scoring with a hot-ID cache and live param sync.

    ``source`` snapshots carry ``{"table": EmbeddingTable,
    "mlp": params}`` (see :func:`init_scoring_params`); a raw params dict
    is wrapped in a StaticSource.  ``config.cache_capacity`` sizes the
    hot-ID cache (0 = no cache, every lookup streams)."""

    def __init__(self, source: ParamSource | dict, *,
                 config: ServingConfig | None = None,
                 stream: StreamConfig | None = None):
        if not isinstance(source, ParamSource):
            source = StaticSource(source)
        self.source = source
        self.config = config or ServingConfig()
        self.stream = stream
        snap = source.snapshot()
        self.dim = int(snap.params["table"][0].shape[1])
        self._table = _as_table(snap.params["table"])
        self._mlp = snap.params["mlp"]
        self._version = snap.version
        self.param_step = snap.step
        self._n_mlp = sum(1 for k in self._mlp if k.startswith("w"))
        self.cache = (HotIDCache(self.config.cache_capacity, self.dim)
                      if self.config.cache_capacity else None)
        if self.cache is not None:
            self.cache.bump_version(snap.version)
        self._sync_lock = threading.Lock()
        self.requests = 0
        self.scored = 0
        self.syncs_adopted = 0
        self.latencies_us: list[float] = []
        # the dense tower is jitted once; (B, D) -> (B,) score
        n_layers = self._n_mlp
        self._tower = jax.jit(
            lambda p, x: jax.nn.sigmoid(_mlp_fwd(p, x, n_layers)[:, 0]))
        source.add_listener(self._on_sync)

    # -- live sync ---------------------------------------------------------
    def _on_sync(self, snap: Snapshot, touched: Any) -> None:
        """Runs on the SYNC thread after each version swap: adopt the new
        table/tower and drop exactly the cache rows the update touched.
        The lock only guards the (table, mlp, version) triple becoming
        visible together — the scoring hot path holds it for a reference
        copy, never across a kernel call."""
        table = _as_table(snap.params["table"])
        with self._sync_lock:
            self._table = table
            self._mlp = snap.params["mlp"]
            self._version = snap.version
            self.param_step = snap.step
            self.syncs_adopted += 1
        if self.cache is not None:
            self.cache.bump_version(snap.version, touched)

    def _pin(self) -> tuple[EmbeddingTable, Any, int]:
        with self._sync_lock:
            return self._table, self._mlp, self._version

    # -- scoring hot path --------------------------------------------------
    def score(self, raw_ids: np.ndarray) -> np.ndarray:
        """(B, F) raw categorical IDs -> (B,) f32 scores, all under ONE
        pinned parameter version."""
        t0 = time.perf_counter()
        table, mlp, version = self._pin()
        hashed = np.asarray(hash_ids(jnp.asarray(raw_ids, jnp.int32),
                                     table.table.shape[0]))
        pooled = cached_pooled_lookup(self.cache, table, hashed,
                                      version=version, stream=self.stream,
                                      dim=self.dim)
        out = np.asarray(self._tower(mlp, jnp.asarray(pooled)))
        self.requests += 1
        self.scored += out.shape[0]
        self.latencies_us.append((time.perf_counter() - t0) * 1e6)
        return out

    def close(self, grace: float = 1.0) -> None:
        self.source.close(grace)

    def stats(self) -> dict:
        lat = np.asarray(self.latencies_us, np.float64)
        with self._sync_lock:
            # one consistent view: a sync between these reads could
            # otherwise pair the new version with the old step
            version, step, adopted = (self._version, self.param_step,
                                      self.syncs_adopted)
        out = {
            "requests": self.requests,
            "scored": self.scored,
            "param_version": version,
            "param_step": step,
            "syncs_adopted": adopted,
            "hit_rate": self.cache.hit_rate if self.cache else 0.0,
            "cache_rows": len(self.cache) if self.cache else 0,
            "cache_bytes": self.cache.nbytes if self.cache else 0,
        }
        if lat.size:
            out["p50_us"] = float(np.percentile(lat, 50))
            out["p99_us"] = float(np.percentile(lat, 99))
        return out
