"""The paper's own recommendation models: DeepFM, YouTubeDNN, DIEN.

These are the models GBA actually trains (Tab. 5.1).  Each is a pure
function of ``(params, batch) -> logit`` where ``batch`` is a dict of hashed
categorical IDs (+ label).  The sparse module is the hashed embedding table
(``params["embed"]`` and, for DeepFM, ``params["linear"]``); everything else
is the dense module — exactly the paper's sparse/dense split, which GBA's
per-ID staleness decay relies on.  The table lookups run under
``jax.named_scope("embedding")`` and the rest of each forward pass and the
loss under ``"dense"``, so the compiled step's ops (backward ones as
``transpose(jvp(embedding))``) name the module they belong to.

Batch layout (from repro.data.clickstream):
  fields:   (B, num_fields) int32   hashed categorical features
  behavior: (B, behavior_len) int32 hashed behavior-sequence IDs (DIEN/YTB)
  target:   (B,) int32              hashed target-item ID (DIEN/YTB)
  label:    (B,) float32            click label
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.recsys import RecsysConfig

Params = dict[str, Any]


def _mlp_init(key, dims: tuple[int, ...]) -> Params:
    ks = jax.random.split(key, len(dims) - 1)
    return {
        f"w{i}": jax.random.normal(ks[i], (dims[i], dims[i + 1]),
                                   jnp.float32) / math.sqrt(dims[i])
        for i in range(len(dims) - 1)
    } | {f"b{i}": jnp.zeros((dims[i + 1],), jnp.float32)
         for i in range(len(dims) - 1)}


def _mlp_fwd(p: Params, x: jax.Array, n: int, final_act: bool = False
             ) -> jax.Array:
    for i in range(n):
        x = x @ p[f"w{i}"] + p[f"b{i}"]
        if i < n - 1 or final_act:
            x = jax.nn.relu(x)
    return x


# ---------------------------------------------------------------------------
# DeepFM (Criteo task)
# ---------------------------------------------------------------------------

def init_deepfm(key, cfg: RecsysConfig) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    deep_in = cfg.num_fields * cfg.embed_dim
    dims = (deep_in, *cfg.mlp_dims, 1)
    return {
        "embed": jax.random.normal(k1, (cfg.hash_capacity, cfg.embed_dim),
                                   jnp.float32) * 0.01,
        "linear": jax.random.normal(k2, (cfg.hash_capacity,),
                                    jnp.float32) * 0.01,
        "bias": jnp.zeros((), jnp.float32),
        "mlp": _mlp_init(k3, dims),
    }


def deepfm_logit(params: Params, cfg: RecsysConfig, batch: dict) -> jax.Array:
    ids = batch["fields"]                               # (B, F)
    with jax.named_scope("embedding"):
        e = params["embed"][ids]                        # (B, F, D)
        lin = params["linear"][ids]                     # (B, F)
    with jax.named_scope("dense"):
        # first order
        first = lin.sum(axis=1)                         # (B,)
        # FM second order: 0.5 * ((sum e)^2 - sum e^2)
        s = e.sum(axis=1)
        fm = 0.5 * (jnp.square(s) - jnp.square(e).sum(axis=1)).sum(axis=-1)
        # deep
        deep_in = e.reshape(e.shape[0], -1)
        n = len(cfg.mlp_dims) + 1
        deep = _mlp_fwd(params["mlp"], deep_in, n)[:, 0]
        return params["bias"] + first + fm + deep


# ---------------------------------------------------------------------------
# YouTubeDNN (Private task)
# ---------------------------------------------------------------------------

def _lookup(params: Params, batch: dict
            ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Field, behaviour-sequence and target rows of the shared table:
    (B, F, D), (B, L, D), (B, D)."""
    with jax.named_scope("embedding"):
        return (params["embed"][batch["fields"]],
                params["embed"][batch["behavior"]],
                params["embed"][batch["target"]])


def init_youtubednn(key, cfg: RecsysConfig) -> Params:
    k1, k2 = jax.random.split(key)
    mlp_in = (cfg.num_fields + 2) * cfg.embed_dim  # fields + pooled + target
    dims = (mlp_in, *cfg.mlp_dims, 1)
    return {
        "embed": jax.random.normal(k1, (cfg.hash_capacity, cfg.embed_dim),
                                   jnp.float32) * 0.01,
        "mlp": _mlp_init(k2, dims),
    }


def youtubednn_logit(params: Params, cfg: RecsysConfig, batch: dict
                     ) -> jax.Array:
    e_fields, e_beh, e_tgt = _lookup(params, batch)
    with jax.named_scope("dense"):
        pooled = e_beh.mean(axis=1)
        x = jnp.concatenate(
            [e_fields.reshape(e_fields.shape[0], -1), pooled, e_tgt],
            axis=-1)
        n = len(cfg.mlp_dims) + 1
        return _mlp_fwd(params["mlp"], x, n)[:, 0]


# ---------------------------------------------------------------------------
# DIEN (Alimama task) — GRU interest extraction + attention evolution (lite)
# ---------------------------------------------------------------------------

def _gru_init(key, d_in: int, d_h: int) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "wx": jax.random.normal(k1, (d_in, 3 * d_h), jnp.float32)
        / math.sqrt(d_in),
        "wh": jax.random.normal(k2, (d_h, 3 * d_h), jnp.float32)
        / math.sqrt(d_h),
        "b": jnp.zeros((3 * d_h,), jnp.float32),
    }


def _gru_scan(p: Params, xs: jax.Array) -> jax.Array:
    """xs: (B, L, Din) -> hidden states (B, L, Dh)."""
    d_h = p["wh"].shape[0]
    B = xs.shape[0]

    def step(h, x):
        gx = x @ p["wx"] + p["b"]
        gh = h @ p["wh"]
        r = jax.nn.sigmoid(gx[:, :d_h] + gh[:, :d_h])
        z = jax.nn.sigmoid(gx[:, d_h:2 * d_h] + gh[:, d_h:2 * d_h])
        n = jnp.tanh(gx[:, 2 * d_h:] + r * gh[:, 2 * d_h:])
        h = (1 - z) * n + z * h
        return h, h

    _, hs = lax.scan(step, jnp.zeros((B, d_h), jnp.float32),
                     jnp.moveaxis(xs, 1, 0))
    return jnp.moveaxis(hs, 0, 1)


def init_dien(key, cfg: RecsysConfig) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    D = cfg.embed_dim
    mlp_in = cfg.num_fields * D + D + D   # fields + final interest + target
    dims = (mlp_in, *cfg.mlp_dims, 1)
    return {
        "embed": jax.random.normal(k1, (cfg.hash_capacity, D),
                                   jnp.float32) * 0.01,
        "gru": _gru_init(k2, D, D),
        "att_w": jax.random.normal(k3, (D, D), jnp.float32) / math.sqrt(D),
        "mlp": _mlp_init(k4, dims),
    }


def dien_logit(params: Params, cfg: RecsysConfig, batch: dict) -> jax.Array:
    e_fields, e_beh, e_tgt = _lookup(params, batch)
    with jax.named_scope("dense"):
        hs = _gru_scan(params["gru"], e_beh)            # (B, L, D)
        # target-conditioned attention over interest states
        att = jnp.einsum("bld,de,be->bl", hs, params["att_w"], e_tgt)
        att = jax.nn.softmax(att, axis=-1)
        interest = jnp.einsum("bl,bld->bd", att, hs)
        x = jnp.concatenate(
            [e_fields.reshape(e_fields.shape[0], -1), interest, e_tgt],
            axis=-1)
        n = len(cfg.mlp_dims) + 1
        return _mlp_fwd(params["mlp"], x, n)[:, 0]


# ---------------------------------------------------------------------------
# uniform interface
# ---------------------------------------------------------------------------

_INIT = {"deepfm": init_deepfm, "youtubednn": init_youtubednn,
         "dien": init_dien}
_LOGIT = {"deepfm": deepfm_logit, "youtubednn": youtubednn_logit,
          "dien": dien_logit}


def init_recsys(key, cfg: RecsysConfig) -> Params:
    return _INIT[cfg.model](key, cfg)


def recsys_logit(params: Params, cfg: RecsysConfig, batch: dict) -> jax.Array:
    return _LOGIT[cfg.model](params, cfg, batch)


def bce_loss(params: Params, cfg: RecsysConfig, batch: dict) -> jax.Array:
    logit = recsys_logit(params, cfg, batch)
    label = batch["label"]
    with jax.named_scope("dense"):
        return jnp.mean(jnp.maximum(logit, 0) - logit * label
                        + jnp.log1p(jnp.exp(-jnp.abs(logit))))


def sparse_dense_split(params: Params) -> tuple[set[str], set[str]]:
    """Top-level param names belonging to the sparse vs dense module."""
    sparse = {k for k in params if k in ("embed", "linear")}
    dense = set(params) - sparse
    return sparse, dense
