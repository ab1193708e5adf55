"""The paper's own recommendation models: DeepFM, YouTubeDNN, DIEN.

These are the models GBA actually trains (Tab. 5.1).  Each is a pure
function of ``(params, batch) -> logit`` where ``batch`` is a dict of hashed
categorical IDs (+ label).  The sparse module is the hashed embedding table
(``params["embed"]`` and, for DeepFM, ``params["linear"]``); everything else
is the dense module — exactly the paper's sparse/dense split, which GBA's
per-ID staleness decay relies on.

Each model is split at its table lookup.  ``lookup`` gathers a batch's
rows, in the order ``flat_ids`` lists the ids; the ``*_from_rows``
functions compute the rest of the model and its loss from those rows and
the dense module alone, so a trainer can differentiate the loss by the
gathered rows and never by the table.  ``recsys_logit`` and
``recsys_loss`` (the loss each model trains on) compose the two.  The
lookup runs under ``jax.named_scope("embedding")`` and the rest of each
forward pass and the loss under ``"dense"`` (DIEN's interest layers under
``"dense/interest"``), so the compiled step's ops (backward ones as
``transpose(jvp(dense))``) name the module they belong to.

Batch layout (from repro.data.clickstream):
  fields:   (B, num_fields) int32   hashed categorical features
  behavior: (B, behavior_len) int32 hashed behavior-sequence IDs (DIEN/YTB;
                                    DIEN: (item, category) pairs interleaved)
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


SPARSE_KEYS = ("embed", "linear")   # the sparse module's tables


def flat_ids(batch: dict) -> jax.Array:
    """The ids each example looks up, (B, n_ids): its fields, then the
    behaviour sequence and the target where the batch has them."""
    parts = [batch["fields"]]
    if "behavior" in batch:
        parts += [batch["behavior"], batch["target"][:, None]]
    return jnp.concatenate(parts, axis=1)


def ids_per_example(cfg: RecsysConfig) -> int:
    """``flat_ids``' n_ids for a batch drawn for ``cfg``."""
    return cfg.num_fields + (cfg.behavior_len + 1 if cfg.behavior_len
                             else 0)


def lookup(params: Params, batch: dict) -> Params:
    """The batch's rows of each table, in ``flat_ids`` order: ``embed``
    (B, n_ids, D) and, for DeepFM, ``linear`` (B, n_ids)."""
    ids = flat_ids(batch)
    with jax.named_scope("embedding"):
        return {k: params[k][ids] for k in SPARSE_KEYS if k in params}


def _split_rows(cfg: RecsysConfig, e: jax.Array
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Field, behaviour-sequence and target rows of ``lookup``'s
    ``embed``: (B, F, D), (B, L, D), (B, D)."""
    f, n = cfg.num_fields, cfg.behavior_len
    return e[:, :f], e[:, f:f + n], e[:, f + n]


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


def deepfm_logit_from_rows(params: Params, cfg: RecsysConfig, rows: Params
                           ) -> jax.Array:
    e, lin = rows["embed"], rows["linear"]              # (B, F, D), (B, F)
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

def init_youtubednn(key, cfg: RecsysConfig) -> Params:
    k1, k2 = jax.random.split(key)
    mlp_in = (cfg.num_fields + 2) * cfg.embed_dim  # fields + pooled + target
    dims = (mlp_in, *cfg.mlp_dims, 1)
    return {
        "embed": jax.random.normal(k1, (cfg.hash_capacity, cfg.embed_dim),
                                   jnp.float32) * 0.01,
        "mlp": _mlp_init(k2, dims),
    }


def youtubednn_logit_from_rows(params: Params, cfg: RecsysConfig,
                               rows: Params) -> jax.Array:
    with jax.named_scope("dense"):
        e_fields, e_beh, e_tgt = _split_rows(cfg, rows["embed"])
        pooled = e_beh.mean(axis=1)
        x = jnp.concatenate(
            [e_fields.reshape(e_fields.shape[0], -1), pooled, e_tgt],
            axis=-1)
        n = len(cfg.mlp_dims) + 1
        return _mlp_fwd(params["mlp"], x, n)[:, 0]


# ---------------------------------------------------------------------------
# DIEN (Alimama task): Zhou et al., arXiv:1809.03672
# ---------------------------------------------------------------------------
#
# A behaviour is an (item, category) pair, i_t = [e(item_t), e(cat_t)] of
# H = 2D columns; ``behavior`` holds the pairs' ids interleaved, ``target``
# the ad's item and ``fields[:, 1]`` its category (the other fields are the
# user's profile).  The interest extractor is a GRU in the paper's gate
# convention, h_t = (1 - u_t) h_{t-1} + u_t h~_t, trained also by the
# auxiliary loss (Eq. 6): each state against the next clicked behaviour and
# a negative, here the next behaviour of example (b + 1) mod B of the same
# batch (the paper samples negatives).  Bilinear attention
# a_t = softmax_t(h_t^T W e_a) scales the update gate of the AUGRU (the
# interest evolution layer), whose last state joins the MLP input
# [e(user), e_a, sum_t i_t, e_a * sum_t i_t, h'_T], as the authors' code
# builds it; the MLP's hidden layers use Dice.  The authors' two-way
# softmax output equals one logit under the binary cross-entropy.  The
# extractor, the auxiliary loss, the attention and the AUGRU run under
# ``jax.named_scope("interest")`` inside ``dense``.

AUX_WEIGHT = 1.0    # alpha of the auxiliary loss, as the authors' code
DICE_EPS = 1e-9     # the authors' ``dice`` epsilon


def _dien_dims(cfg: RecsysConfig) -> tuple[int, int]:
    """(T behaviour pairs, H = 2D), or raises where the batch layout
    cannot hold DIEN's inputs."""
    if cfg.behavior_len < 4 or cfg.behavior_len % 2:
        raise ValueError("DIEN's behavior_len counts (item, category) ids "
                         "of at least two pairs: an even number >= 4, got "
                         f"{cfg.behavior_len}")
    if cfg.num_fields < 2:
        raise ValueError("DIEN reads the user from field 0 and the ad's "
                         f"category from field 1; num_fields is "
                         f"{cfg.num_fields}")
    return cfg.behavior_len // 2, 2 * cfg.embed_dim


def _gru_init(key, d: int) -> Params:
    """Input d, hidden d; the 3d columns are the update gate, the reset
    gate and the candidate state."""
    k1, k2 = jax.random.split(key)
    return {
        "w": jax.random.normal(k1, (d, 3 * d), jnp.float32) / math.sqrt(d),
        "u": jax.random.normal(k2, (d, 3 * d), jnp.float32) / math.sqrt(d),
        "b": jnp.zeros((3 * d,), jnp.float32),
    }


def _gru_scan(p: Params, xs: jax.Array, att: jax.Array | None = None
              ) -> jax.Array:
    """xs: (B, T, H) -> states (B, T, H), from h_0 = 0.  With ``att``
    (B, T), an AUGRU: the update gate is scaled by a_t."""
    d = p["u"].shape[0]
    gx = jnp.moveaxis(xs @ p["w"] + p["b"], 1, 0)           # (T, B, 3H)

    def step(h, inp):
        g, a_t = (inp, None) if att is None else inp
        gh = h @ p["u"]
        u = jax.nn.sigmoid(g[:, :d] + gh[:, :d])
        r = jax.nn.sigmoid(g[:, d:2 * d] + gh[:, d:2 * d])
        cand = jnp.tanh(g[:, 2 * d:] + r * gh[:, 2 * d:])
        if att is not None:
            u = a_t[:, None] * u
        h = (1 - u) * h + u * cand
        return h, h

    h0 = jnp.zeros((xs.shape[0], d), xs.dtype)
    _, hs = lax.scan(step, h0, gx if att is None else (gx, att.T))
    return jnp.moveaxis(hs, 0, 1)


def _aux_loss(hs: jax.Array, beh: jax.Array) -> jax.Array:
    """Eq. 6: -mean over (b, t < T) of log s(<h_t, i_{t+1}>) +
    log(1 - s(<h_t, i^_{t+1}>)), the negative from example (b + 1) mod B."""
    h = hs[:, :-1]
    pos = jnp.sum(h * beh[:, 1:], axis=-1)
    neg = jnp.sum(h * jnp.roll(beh, -1, axis=0)[:, 1:], axis=-1)
    return -jnp.mean(jax.nn.log_sigmoid(pos) + jax.nn.log_sigmoid(-neg))


def _dice(x: jax.Array, alpha: jax.Array) -> jax.Array:
    """Dice over the batch's own statistics, as the authors' ``dice``
    computes them in training."""
    mean = jnp.mean(x, axis=0)
    std = jnp.sqrt(jnp.mean(jnp.square(x - mean), axis=0) + DICE_EPS)
    p = jax.nn.sigmoid((x - mean) / (std + DICE_EPS))
    return p * x + (1 - p) * alpha * x


def init_dien(key, cfg: RecsysConfig) -> Params:
    _, h = _dien_dims(cfg)
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    d = cfg.embed_dim
    dims = ((cfg.num_fields - 1) * d + 4 * h, *cfg.mlp_dims, 1)
    mlp = _mlp_init(k5, dims) | {
        f"dice{i}": jnp.zeros((n,), jnp.float32)
        for i, n in enumerate(cfg.mlp_dims)}
    return {
        "embed": jax.random.normal(k1, (cfg.hash_capacity, d),
                                   jnp.float32) * 0.01,
        "gru": _gru_init(k2, h),
        "att": jax.random.normal(k3, (h, h), jnp.float32) / math.sqrt(h),
        "augru": _gru_init(k4, h),
        "mlp": mlp,
    }


def _dien_from_rows(params: Params, cfg: RecsysConfig, rows: Params
                    ) -> tuple[jax.Array, jax.Array]:
    """(logit (B,), auxiliary loss ())."""
    t, h = _dien_dims(cfg)
    with jax.named_scope("dense"):
        e_fields, e_beh, e_tgt = _split_rows(cfg, rows["embed"])
        b = e_fields.shape[0]
        beh = e_beh.reshape(b, t, h)                        # i_t
        ad = jnp.concatenate([e_tgt, e_fields[:, 1]], axis=-1)
        user = jnp.concatenate([e_fields[:, :1], e_fields[:, 2:]], axis=1)
        with jax.named_scope("interest"):
            hs = _gru_scan(params["gru"], beh)
            aux = _aux_loss(hs, beh)
            q = ad @ params["att"].T                        # W e_a
            att = jax.nn.softmax(jnp.sum(hs * q[:, None], axis=-1), axis=-1)
            final = _gru_scan(params["augru"], hs, att)[:, -1]
        pooled = beh.sum(axis=1)
        x = jnp.concatenate([user.reshape(b, -1), ad, pooled, ad * pooled,
                             final], axis=-1)
        p, n = params["mlp"], len(cfg.mlp_dims) + 1
        for i in range(n):
            x = x @ p[f"w{i}"] + p[f"b{i}"]
            if i < n - 1:
                x = _dice(x, p[f"dice{i}"])
        return x[:, 0], aux


def dien_logit_from_rows(params: Params, cfg: RecsysConfig, rows: Params
                         ) -> jax.Array:
    return _dien_from_rows(params, cfg, rows)[0]


def dien_loss_from_rows(params: Params, cfg: RecsysConfig, rows: Params,
                        batch: dict) -> jax.Array:
    logit, aux = _dien_from_rows(params, cfg, rows)
    with jax.named_scope("dense"):
        return _bce(logit, batch["label"]) + AUX_WEIGHT * aux


# ---------------------------------------------------------------------------
# uniform interface
# ---------------------------------------------------------------------------

_INIT = {"deepfm": init_deepfm, "youtubednn": init_youtubednn,
         "dien": init_dien}
_LOGIT_FROM_ROWS = {"deepfm": deepfm_logit_from_rows,
                    "youtubednn": youtubednn_logit_from_rows,
                    "dien": dien_logit_from_rows}


def init_recsys(key, cfg: RecsysConfig) -> Params:
    return _INIT[cfg.model](key, cfg)


def recsys_logit_from_rows(params: Params, cfg: RecsysConfig, rows: Params
                           ) -> jax.Array:
    return _LOGIT_FROM_ROWS[cfg.model](params, cfg, rows)


def recsys_logit(params: Params, cfg: RecsysConfig, batch: dict) -> jax.Array:
    return recsys_logit_from_rows(params, cfg, lookup(params, batch))


def _bce(logit: jax.Array, label: jax.Array) -> jax.Array:
    return jnp.mean(jnp.maximum(logit, 0) - logit * label
                    + jnp.log1p(jnp.exp(-jnp.abs(logit))))


def bce_loss_from_rows(params: Params, cfg: RecsysConfig, rows: Params,
                       batch: dict) -> jax.Array:
    logit = recsys_logit_from_rows(params, cfg, rows)
    with jax.named_scope("dense"):
        return _bce(logit, batch["label"])


# models whose training loss is more than the cross-entropy of the logit
_LOSS_FROM_ROWS = {"dien": dien_loss_from_rows}


def recsys_loss_from_rows(params: Params, cfg: RecsysConfig, rows: Params,
                          batch: dict) -> jax.Array:
    """The mean training loss of a batch from its ``lookup`` rows; of
    ``params`` only the dense module is read."""
    return _LOSS_FROM_ROWS.get(cfg.model, bce_loss_from_rows)(
        params, cfg, rows, batch)


def bce_loss(params: Params, cfg: RecsysConfig, batch: dict) -> jax.Array:
    return bce_loss_from_rows(params, cfg, lookup(params, batch), batch)


def dien_loss(params: Params, cfg: RecsysConfig, batch: dict) -> jax.Array:
    return dien_loss_from_rows(params, cfg, lookup(params, batch), batch)


def recsys_loss(params: Params, cfg: RecsysConfig, batch: dict) -> jax.Array:
    """The mean training loss: ``bce_loss``, plus DIEN's auxiliary loss."""
    return recsys_loss_from_rows(params, cfg, lookup(params, batch), batch)


def sparse_dense_split(params: Params) -> tuple[set[str], set[str]]:
    """Top-level param names belonging to the sparse vs dense module."""
    sparse = {k for k in params if k in SPARSE_KEYS}
    dense = set(params) - sparse
    return sparse, dense
