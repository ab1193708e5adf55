"""The benchmark's own click stream: a copy of ``repro.data.clickstream``.

Every batch is a pure function of ``(seed, day, index)`` and equals, id for
id and label for label, what the program's ``ClickStream`` gives for the
same arguments (``chipbench/tests/test_bench_traffic.py`` checks it).  The
one change: the Zipf CDF is built once here, where the program's
``rng.choice(V, p=...)`` rebuilds it on every draw (O(V) a draw).
``Generator.choice`` draws ``random(shape)`` and searches the normalised
cumulative sum with ``side="right"``, so the ids are the same.
"""
from __future__ import annotations

import numpy as np


class ClickStream:
    """Synthetic Zipf-skewed click log with a logistic ground truth."""

    LATENT = 8  # latent width of the ground-truth model

    def __init__(self, *, hash_capacity: int, num_fields: int,
                 behavior_len: int, seed: int, zipf_a: float, num_days: int,
                 batch_size: int, drift: float):
        self.hash_capacity = hash_capacity
        self.num_fields = num_fields
        self.behavior_len = behavior_len
        self.seed = seed
        self.num_days = num_days
        self.batch_size = batch_size
        rng = np.random.default_rng(seed)
        V, D = hash_capacity, self.LATENT
        self._id_factors = rng.normal(0, 1, (V, D)).astype(np.float32)
        self._field_w = rng.normal(0, 1, (num_fields, D)).astype(np.float32)
        self._beh_w = rng.normal(0, 1, (D,)).astype(np.float32)
        self._day_drift = rng.normal(0, drift, (num_days, D)).astype(
            np.float32)
        ranks = np.arange(1, V + 1, dtype=np.float64)
        probs = ranks ** (-zipf_a)
        probs = (probs / probs.sum()).astype(np.float64)
        cdf = probs.cumsum()
        self._cdf = cdf / cdf[-1]

    def _draw_ids(self, rng: np.random.Generator, shape) -> np.ndarray:
        u = rng.random(shape)
        return self._cdf.searchsorted(u, side="right").astype(np.int32)

    def batch(self, day: int, index: int) -> dict[str, np.ndarray]:
        """Pure function of (seed, day, index)."""
        bs = self.batch_size
        rng = np.random.default_rng(
            (self.seed * 1_000_003 + day) * 1_000_003 + index)
        fields = self._draw_ids(rng, (bs, self.num_fields))
        out = {"fields": fields}
        logit = (self._id_factors[fields] * self._field_w[None]).sum(
            axis=(1, 2)) / np.sqrt(self.num_fields)
        if self.behavior_len:
            behavior = self._draw_ids(rng, (bs, self.behavior_len))
            target = self._draw_ids(rng, (bs,))
            out["behavior"] = behavior
            out["target"] = target
            aff = (self._id_factors[behavior].mean(axis=1)
                   * self._id_factors[target]).sum(axis=-1)
            logit = logit + aff * 2.0
        drift = self._day_drift[day % self.num_days]
        logit = logit + (self._id_factors[fields[:, 0]] * drift).sum(axis=-1)
        logit = logit - 1.0
        p = 1.0 / (1.0 + np.exp(-logit))
        out["label"] = (rng.uniform(size=bs) < p).astype(np.float32)
        return out
