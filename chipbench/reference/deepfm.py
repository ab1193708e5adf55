"""Plain DeepFM (arXiv:1703.04247) as the configuration defines it: a
first-order term and an FM second-order term over the field embeddings,
plus an MLP over their concatenation.  Also the benchmark's weights: made
from the seed here and handed to both the program and this reference.

``batch``: ``fields`` (B, F) int32 hashed ids, ``label`` (B,) float.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference.precision import dot

# the sparse module: the hashed tables, aggregated row by row
SPARSE = ("embed", "linear")
# every matrix product goes through precision.dot
MATMULS_VIA_DOT = True


def mlp_init(key, dims) -> dict:
    ks = jax.random.split(key, len(dims) - 1)
    w = {f"w{i}": jax.random.normal(ks[i], (dims[i], dims[i + 1]),
                                    jnp.float32) / math.sqrt(dims[i])
         for i in range(len(dims) - 1)}
    b = {f"b{i}": jnp.zeros((dims[i + 1],), jnp.float32)
         for i in range(len(dims) - 1)}
    return w | b


def mlp(p: dict, x: jax.Array, n: int) -> jax.Array:
    for i in range(n):
        x = dot(x, p[f"w{i}"]) + p[f"b{i}"]
        if i < n - 1:
            x = jax.nn.relu(x)
    return x


def init(key, cfg: dict) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    v, d, f = cfg["hash_capacity"], cfg["embed_dim"], cfg["num_fields"]
    return {
        "embed": jax.random.normal(k1, (v, d), jnp.float32) * 0.01,
        "linear": jax.random.normal(k2, (v,), jnp.float32) * 0.01,
        "bias": jnp.zeros((), jnp.float32),
        "mlp": mlp_init(k3, (f * d, *cfg["mlp_dims"], 1)),
    }


def logit(p: dict, cfg: dict, batch: dict) -> jax.Array:
    ids = batch["fields"]
    e = p["embed"][ids]                                  # (B, F, D)
    first = p["linear"][ids].sum(axis=1)
    s = e.sum(axis=1)
    fm = 0.5 * (s * s - (e * e).sum(axis=1)).sum(axis=-1)
    deep = mlp(p["mlp"], e.reshape(e.shape[0], -1),
               len(cfg["mlp_dims"]) + 1)[:, 0]
    return p["bias"] + first + fm + deep


def ids(batch: dict) -> jax.Array:
    """Every hashed id one example touches: (B, n_ids)."""
    return batch["fields"]


def ids_per_example(cfg: dict) -> int:
    return cfg["num_fields"]


def forward_flops(cfg: dict) -> tuple[int, int]:
    """(matmul FLOPs, elementwise FLOPs) of one example's forward pass,
    counted as ``chipbench/work.py`` says."""
    from chipbench.work import mlp_flops
    f, d = cfg["num_fields"], cfg["embed_dim"]
    dims = (f * d, *cfg["mlp_dims"], 1)
    mm = sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    # first order: F-1 adds; FM: sum over fields (F-1)*D adds, its square
    # D, squares F*D, their sum (F-1)*D, difference D, sum over D D-1,
    # halving 1; the logit's three adds
    first = f - 1
    fm = (f - 1) * d + d + f * d + (f - 1) * d + d + (d - 1) + 1
    ew = first + fm + (mlp_flops(dims) - mm) + 3
    return mm, ew
