"""Plain DIEN (Zhou et al., arXiv:1809.03672) as the configuration defines
it, on GBA's Alimama task (arXiv:2205.11048 Tab. 5.1).  Also the
benchmark's weights: made from the seed here and handed to both the
program and this reference.

``batch``: ``fields`` (B, F) int32, field 0 the user, field 1 the ad's
category, the rest the user's profile; ``behavior`` (B, 2T) int32, the
clicked (item, category) pairs interleaved; ``target`` (B,) int32, the
ad's item; ``label`` (B,) float.  D = ``embed_dim``, H = 2D.

- A behaviour i_t = [e(item_t), e(cat_t)] and the ad e_a = [e(target),
  e(ad category)], both H wide, from the one hashed table ``embed``.
- Interest extractor, a GRU (input H, hidden H, h_0 = 0):
  u_t = s(W_u i_t + U_u h_{t-1} + b_u), r_t = s(W_r i_t + U_r h_{t-1} +
  b_r), c_t = tanh(W_c i_t + r_t * (U_c h_{t-1}) + b_c),
  h_t = (1 - u_t) * h_{t-1} + u_t * c_t.
- Auxiliary loss (Eq. 6), weight ``AUX_WEIGHT``: -mean over b and t < T of
  log s(<h_t, i_{t+1}>) + log(1 - s(<h_t, n_{t+1}>)), where the negative
  n_{t+1} is the pair at t+1 of example (b + 1) mod B of the same batch.
- Attention a_t = softmax_t(h_t^T W e_a), W of H x H.
- Interest evolution, an AUGRU with its own weights over the h_t: the
  GRU's gates with the update gate scaled by a_t.
- MLP over [e(user's fields), e_a, sum_t i_t, e_a * sum_t i_t, h'_T]
  (162 wide at D = 18 and two fields), hidden layers with Dice over the
  batch's statistics, one logit.
- Loss: the binary cross-entropy of the logit plus the auxiliary loss.

Every matrix and inner product goes through ``precision.dot``.  The
recurrences run one step after another, each gate with its own slice of
the weights.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference.precision import dot

# the sparse module: the hashed table, aggregated row by row
SPARSE = ("embed",)
# every matrix product goes through precision.dot
MATMULS_VIA_DOT = True
AUX_WEIGHT = 1.0
DICE_EPS = 1e-9
GATES = ("update", "reset", "candidate")   # column blocks of w, u, b


def sizes(cfg: dict) -> tuple[int, int]:
    """(T behaviour pairs, H)."""
    return cfg["behavior_len"] // 2, 2 * cfg["embed_dim"]


def mlp_dims(cfg: dict) -> tuple[int, ...]:
    _, h = sizes(cfg)
    width = (cfg["num_fields"] - 1) * cfg["embed_dim"] + 4 * h
    return (width, *cfg["mlp_dims"], 1)


def gru_init(key, h: int) -> dict:
    k1, k2 = jax.random.split(key)
    return {"w": jax.random.normal(k1, (h, 3 * h), jnp.float32)
            / math.sqrt(h),
            "u": jax.random.normal(k2, (h, 3 * h), jnp.float32)
            / math.sqrt(h),
            "b": jnp.zeros((3 * h,), jnp.float32)}


def init(key, cfg: dict) -> dict:
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    _, h = sizes(cfg)
    dims = mlp_dims(cfg)
    ks = jax.random.split(k5, len(dims) - 1)
    mlp = {}
    for i in range(len(dims) - 1):
        mlp[f"w{i}"] = jax.random.normal(
            ks[i], (dims[i], dims[i + 1]), jnp.float32) / math.sqrt(dims[i])
        mlp[f"b{i}"] = jnp.zeros((dims[i + 1],), jnp.float32)
    for i, n in enumerate(cfg["mlp_dims"]):
        mlp[f"dice{i}"] = jnp.zeros((n,), jnp.float32)
    return {
        "embed": jax.random.normal(k1, (cfg["hash_capacity"],
                                        cfg["embed_dim"]),
                                   jnp.float32) * 0.01,
        "gru": gru_init(k2, h),
        "att": jax.random.normal(k3, (h, h), jnp.float32) / math.sqrt(h),
        "augru": gru_init(k4, h),
        "mlp": mlp,
    }


def inner(a: jax.Array, b: jax.Array) -> jax.Array:
    """<a, b> over the last axis, through ``dot``."""
    return dot(a[..., None, :], b[..., :, None])[..., 0, 0]


def gate(p: dict, name: str, x: jax.Array, h: jax.Array,
         reset: jax.Array | None = None) -> jax.Array:
    """One gate's pre-activation from input x and state h."""
    n = p["u"].shape[0]
    j = GATES.index(name)
    cols = slice(j * n, (j + 1) * n)
    rec = dot(h, p["u"][:, cols])
    if reset is not None:
        rec = reset * rec
    return dot(x, p["w"][:, cols]) + rec + p["b"][cols]


def recurrence(p: dict, xs: jax.Array, att: jax.Array | None = None
               ) -> jax.Array:
    """The GRU (or, with ``att`` (B, T), the AUGRU) over xs (B, T, H):
    every state, (B, T, H)."""

    def step(h, inp):
        x, a = inp
        u = jax.nn.sigmoid(gate(p, "update", x, h))
        r = jax.nn.sigmoid(gate(p, "reset", x, h))
        c = jnp.tanh(gate(p, "candidate", x, h, reset=r))
        u = u * a[:, None]
        h = (1 - u) * h + u * c
        return h, h

    b, t, n = xs.shape
    a = jnp.ones((b, t), xs.dtype) if att is None else att
    h0 = jnp.zeros((b, n), xs.dtype)
    _, hs = jax.lax.scan(step, h0, (jnp.swapaxes(xs, 0, 1), a.T))
    return jnp.swapaxes(hs, 0, 1)


def dice(x: jax.Array, alpha: jax.Array) -> jax.Array:
    """The authors' ``dice`` in training: the batch's mean and
    sqrt(variance + eps)."""
    mean = x.mean(axis=0)
    std = jnp.sqrt(((x - mean) ** 2).mean(axis=0) + DICE_EPS)
    p = jax.nn.sigmoid((x - mean) / (std + DICE_EPS))
    return alpha * (1 - p) * x + p * x


def log_sigmoid(x: jax.Array) -> jax.Array:
    """log s(x) = -log(1 + exp(-x)), stable at both ends."""
    return jnp.minimum(x, 0) - jnp.log1p(jnp.exp(-jnp.abs(x)))


def forward(p: dict, cfg: dict, batch: dict) -> tuple[jax.Array, jax.Array]:
    """(logit (B,), auxiliary loss)."""
    t, h = sizes(cfg)
    table = p["embed"]
    fields = batch["fields"]
    b = fields.shape[0]
    beh = table[batch["behavior"]].reshape(b, t, h)             # i_t
    e_ad = jnp.concatenate([table[batch["target"]],
                            table[fields[:, 1]]], axis=-1)
    profile = jnp.concatenate([fields[:, :1], fields[:, 2:]], axis=1)
    user = table[profile].reshape(b, -1)

    hs = recurrence(p["gru"], beh)
    negatives = jnp.concatenate([beh[1:], beh[:1]], axis=0)
    pos = inner(hs[:, :-1], beh[:, 1:])
    neg = inner(hs[:, :-1], negatives[:, 1:])
    aux = -(log_sigmoid(pos) + log_sigmoid(-neg)).mean()

    w_ad = dot(e_ad, p["att"].T)                                # W e_a
    scores = inner(hs, w_ad[:, None, :])                        # (B, T)
    att = jax.nn.softmax(scores, axis=1)
    h_last = recurrence(p["augru"], hs, att)[:, -1]

    summed = beh.sum(axis=1)
    x = jnp.concatenate([user, e_ad, summed, e_ad * summed, h_last],
                        axis=-1)
    mlp = p["mlp"]
    layers = len(cfg["mlp_dims"]) + 1
    for i in range(layers):
        x = dot(x, mlp[f"w{i}"]) + mlp[f"b{i}"]
        if i < layers - 1:
            x = dice(x, mlp[f"dice{i}"])
    return x[:, 0], aux


def logit(p: dict, cfg: dict, batch: dict) -> jax.Array:
    return forward(p, cfg, batch)[0]


def loss(p: dict, cfg: dict, batch: dict) -> jax.Array:
    z, aux = forward(p, cfg, batch)
    y = batch["label"]
    bce = -(y * log_sigmoid(z) + (1 - y) * log_sigmoid(-z)).mean()
    return bce + AUX_WEIGHT * aux


def ids(batch: dict) -> jax.Array:
    """Every hashed id one example touches: (B, n_ids)."""
    return jnp.concatenate([batch["fields"], batch["behavior"],
                            batch["target"][:, None]], axis=1)


def ids_per_example(cfg: dict) -> int:
    return cfg["num_fields"] + cfg["behavior_len"] + 1


def forward_flops(cfg: dict) -> tuple[int, int]:
    """(matmul FLOPs, elementwise FLOPs) of one example's forward pass and
    auxiliary loss, counted as ``chipbench/work.py`` says."""
    t, h = sizes(cfg)
    dims = mlp_dims(cfg)
    hidden = sum(cfg["mlp_dims"])
    # GRU and AUGRU: input and recurrent products of three gates a step
    mm = 2 * t * (2 * h * 3 * h) * 2
    # the auxiliary loss's two inner products for each t < T
    mm += 2 * (t - 1) * 2 * h
    # attention: W e_a, then its inner product with every h_t
    mm += 2 * h * h + t * 2 * h
    mm += sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    # a GRU step: three gates' two sums (6H), r * (U h) (H), two sigmoids
    # and a tanh (3H), the update (1 - u) h + u c (4H); the AUGRU's a_t * u
    # (H) more
    ew = t * 14 * h + t * 15 * h
    # each t < T: two log-sigmoids of two (4), the negation (1), their sum
    # (1), and its share of the mean (1)
    ew += 7 * (t - 1)
    # softmax over T: exponentials, their sum, the divisions
    ew += 3 * t - 1
    # sum_t i_t and e_a * sum_t i_t
    ew += (t - 1) * h + h
    # MLP biases, and Dice on every hidden unit: mean and variance shares
    # (3), centring and scaling (2), sigmoid (1), p x + (1 - p) alpha x (5)
    ew += sum(dims[1:]) + 11 * hidden
    return mm, ew
