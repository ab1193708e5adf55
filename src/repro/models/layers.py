"""Layer zoo: norms, RoPE, GQA attention (global / sliding-window / cross),
gated MLP, MoE, and the Mamba2/SSD mixer.

Everything is pure-functional: ``init_*`` builds a params pytree (nested
dicts of jnp arrays), ``*_fwd`` applies it.  Attention layers support three
modes:

* full-sequence (training / prefill) — causal (+ optional window) mask;
* decode — one new token against a KV cache.  Global layers keep a full
  ``(B, cache_len, kv, hd)`` cache; local layers keep a **ring buffer** of
  ``window`` entries so a 500k-token context costs O(window) memory.

Keys are RoPE'd at insert time so ring-buffer rotation never needs to
re-rotate history.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig

Params = dict[str, Any]

_MASK_VALUE = -2.0e38


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def _dense_init(key, shape, dtype, scale: float | None = None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def dtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, dim: int | None = None) -> Params:
    dim = dim or cfg.d_model
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.zeros((dim,), jnp.float32)}
    return {"scale": jnp.zeros((dim,), jnp.float32),
            "bias": jnp.zeros((dim,), jnp.float32)}


def norm_fwd(p: Params, x: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    if "bias" in p:  # layernorm
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * lax.rsqrt(var + 1e-6)
        y = y * (1.0 + p["scale"]) + p["bias"]
    else:            # rmsnorm (gemma-style 1+scale)
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * lax.rsqrt(ms + 1e-6) * (1.0 + p["scale"])
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq)."""
    head_dim = x.shape[-1]
    half = head_dim // 2
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, half)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA; global / local / cross)
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, cross: bool = False) -> Params:
    dt = dtype_of(cfg)
    hd = cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    kv_src = cfg.d_model  # cross-attn keys come from encoder/vision states of d_model
    return {
        "wq": _dense_init(k1, (cfg.d_model, cfg.num_heads, hd), dt),
        "wk": _dense_init(k2, (kv_src, cfg.num_kv_heads, hd), dt),
        "wv": _dense_init(k3, (kv_src, cfg.num_kv_heads, hd), dt),
        "wo": _dense_init(k4, (cfg.num_heads, hd, cfg.d_model), dt),
    }


def _sdpa(q, k, v, mask, softcap: float) -> jax.Array:
    """q: (B,S,Hkv,G,hd)  k/v: (B,T,Hkv,hd)  mask: (B,S,T) bool or None."""
    hd = q.shape[-1]
    scores = jnp.einsum("bsngh,btnh->bnsgt", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(hd)
    if softcap:
        scores = jnp.tanh(scores / softcap) * softcap
    if mask is not None:
        # scores are (B,Hkv,S,G,T); expand mask to (B,1,S,1,T)
        scores = jnp.where(mask[:, None, :, None, :], scores, _MASK_VALUE)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bnsgt,btnh->bsngh", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out


def attention_fwd(p: Params, cfg: ModelConfig, x: jax.Array,
                  positions: jax.Array, *, window: int = 0,
                  kv_override: jax.Array | None = None,
                  return_kv: bool = False):
    """Full-sequence attention. x: (B,S,D). kv_override: cross-attn memory.
    With ``return_kv`` also returns the (RoPE'd) k/v for cache prefill."""
    B, S, _ = x.shape
    G = cfg.num_heads // cfg.num_kv_heads
    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"])
    kv_in = x if kv_override is None else kv_override
    k = jnp.einsum("btd,dnh->btnh", kv_in, p["wk"])
    v = jnp.einsum("btd,dnh->btnh", kv_in, p["wv"])
    if kv_override is None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        t_pos = positions
        q_pos = positions
        causal = q_pos[:, :, None] >= t_pos[:, None, :]
        if window:
            causal &= q_pos[:, :, None] - t_pos[:, None, :] < window
        mask = causal
    else:
        mask = None  # cross-attn: attend to all memory tokens
    q = q.reshape(B, S, cfg.num_kv_heads, G, cfg.resolved_head_dim)
    out = _sdpa(q, k, v, mask, cfg.attn_softcap)
    out = out.reshape(B, S, cfg.num_heads, cfg.resolved_head_dim)
    y = jnp.einsum("bsnh,nhd->bsd", out, p["wo"]).astype(x.dtype)
    if return_kv:
        return y, (k, v)
    return y


def kv_to_cache(cfg: ModelConfig, k: jax.Array, v: jax.Array, seq_len: int,
                cache_len: int, window: int = 0) -> Params:
    """Convert full-sequence k/v (B,S,kv,hd) into the decode cache layout of
    capacity ``cache_len``.  Local layers keep the last ``window`` entries
    ring-ordered so ``slot = pos % window`` holds position ``pos`` (matches
    attention_decode's ring addressing)."""
    dt = dtype_of(cfg)
    if window:
        L_cap = min(window, cache_len)
        if seq_len >= L_cap:
            k_last, v_last = k[:, -L_cap:], v[:, -L_cap:]
            shift = seq_len % L_cap
            return {"k": jnp.roll(k_last, shift, axis=1).astype(dt),
                    "v": jnp.roll(v_last, shift, axis=1).astype(dt)}
        pad = L_cap - seq_len
        # positions 0..S-1 land at slots 0..S-1 (pos % L_cap = pos)
        return {"k": jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))
                             ).astype(dt),
                "v": jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))
                             ).astype(dt)}
    pad = cache_len - seq_len
    assert pad >= 0, f"cache_len {cache_len} < prompt {seq_len}"
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return {"k": k.astype(dt), "v": v.astype(dt)}


def init_attn_cache(cfg: ModelConfig, batch: int, cache_len: int,
                    window: int = 0, dtype=None) -> Params:
    """KV cache for one attention layer.  Local layers ring-buffer to
    ``window`` entries; global layers keep ``cache_len``."""
    dt = dtype or dtype_of(cfg)
    L = min(window, cache_len) if window else cache_len
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, L, cfg.num_kv_heads, hd), dt),
        "v": jnp.zeros((batch, L, cfg.num_kv_heads, hd), dt),
    }


def attention_decode(p: Params, cfg: ModelConfig, x: jax.Array,
                     cache: Params, pos: jax.Array, *, window: int = 0,
                     kv_override: jax.Array | None = None
                     ) -> tuple[jax.Array, Params]:
    """One-token decode.  x: (B,1,D); pos: scalar int32 OR per-sequence
    (B,) vector (continuous batching: ragged slot positions)."""
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    G = cfg.num_heads // cfg.num_kv_heads
    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"])
    if kv_override is not None:
        k = jnp.einsum("btd,dnh->btnh", kv_override, p["wk"])
        v = jnp.einsum("btd,dnh->btnh", kv_override, p["wv"])
        q = q.reshape(B, 1, cfg.num_kv_heads, G, hd)
        out = _sdpa(q, k, v, None, cfg.attn_softcap)
        out = out.reshape(B, 1, cfg.num_heads, hd)
        return jnp.einsum("bsnh,nhd->bsd", out, p["wo"]).astype(x.dtype), cache

    pos_vec = jnp.broadcast_to(pos, (B,)) if pos.ndim == 0 else pos
    posb = pos_vec[:, None]                                   # (B, 1)
    q = rope(q, posb, cfg.rope_theta)
    k_new = jnp.einsum("bsd,dnh->bsnh", x, p["wk"])
    v_new = jnp.einsum("bsd,dnh->bsnh", x, p["wv"])
    k_new = rope(k_new, posb, cfg.rope_theta)

    L = cache["k"].shape[1]
    slot_vec = (pos_vec % L) if window else pos_vec           # (B,)
    rows = jnp.arange(B)
    k_cache = cache["k"].at[rows, slot_vec].set(
        k_new[:, 0].astype(cache["k"].dtype), mode="drop")
    v_cache = cache["v"].at[rows, slot_vec].set(
        v_new[:, 0].astype(cache["v"].dtype), mode="drop")
    idx = jnp.arange(L)
    if window:
        # ring buffer: entry at idx holds absolute position p satisfying
        # p % L == idx and pos - L < p <= pos
        abs_pos = pos_vec[:, None] - ((pos_vec[:, None] - idx[None, :]) % L)
        valid = (abs_pos >= 0) & (abs_pos <= pos_vec[:, None])  # (B, L)
    else:
        valid = idx[None, :] <= pos_vec[:, None]                # (B, L)
    mask = valid[:, None, :]
    q = q.reshape(B, 1, cfg.num_kv_heads, G, hd)
    out = _sdpa(q, k_cache, v_cache, mask, cfg.attn_softcap)
    out = out.reshape(B, 1, cfg.num_heads, hd)
    y = jnp.einsum("bsnh,nhd->bsd", out, p["wo"]).astype(x.dtype)
    return y, {"k": k_cache, "v": v_cache}


# ---------------------------------------------------------------------------
# gated MLP
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, d_ff: int | None = None) -> Params:
    dt = dtype_of(cfg)
    d_ff = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi_gate": _dense_init(k1, (cfg.d_model, d_ff), dt),
        "wi_up": _dense_init(k2, (cfg.d_model, d_ff), dt),
        "wo": _dense_init(k3, (d_ff, cfg.d_model), dt),
    }


def mlp_fwd(p: Params, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ p["wi_gate"]) * (x @ p["wi_up"])
    return (h @ p["wo"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# MoE (top-k router, capacity dispatch via scatter/gather)
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig) -> Params:
    dt = dtype_of(cfg)
    E, D, F = cfg.num_experts, cfg.d_model, cfg.d_ff
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "router": _dense_init(k1, (D, E), jnp.float32),
        "wi_gate": _dense_init(k2, (E, D, F), dt),
        "wi_up": _dense_init(k3, (E, D, F), dt),
        "wo": _dense_init(k4, (E, F, D), dt),
    }


def moe_fwd(p: Params, cfg: ModelConfig, x: jax.Array
            ) -> tuple[jax.Array, jax.Array]:
    """Returns (output, aux_load_balance_loss).  x: (B,S,D)."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    T = B * S
    xt = x.reshape(T, D)
    logits = (xt.astype(jnp.float32) @ p["router"])            # (T, E)
    weights, sel = lax.top_k(jax.nn.softmax(logits, axis=-1), K)  # (T, K)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

    # load-balance aux loss (Switch-style)
    probs_mean = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=0)   # (E,)
    counts = jnp.zeros((E,), jnp.float32).at[sel.reshape(-1)].add(1.0)
    frac = counts / (T * K)
    aux = E * jnp.sum(frac * probs_mean)

    # capacity-based dispatch: slot index = rank of the token within its
    # expert's queue.  Computed by stable sort + histogram (the textbook
    # cumsum over the (T*K, E) one-hot costs O(T*K*E) — 1.7e15
    # FLOPs/device for kimi-k2, 586x the expert matmuls themselves; the
    # sort-based ranking is O(T*K log T*K) and numerically identical).
    cap = max(1, int(T * K / E * cfg.moe_capacity_factor))
    flat_sel = sel.reshape(-1)                                  # (T*K,)
    tk = flat_sel.shape[0]
    order = jnp.argsort(flat_sel, stable=True)
    counts_i = jnp.zeros((E,), jnp.int32).at[flat_sel].add(1)
    starts = jnp.cumsum(counts_i) - counts_i                    # (E,)
    ranks_sorted = jnp.arange(tk, dtype=jnp.int32) \
        - starts[flat_sel[order]]
    flat_slot = jnp.zeros((tk,), jnp.int32).at[order].set(ranks_sorted)
    keep = flat_slot < cap

    src = jnp.repeat(xt, K, axis=0)                             # (T*K, D)
    expert_in = jnp.zeros((E, cap, D), x.dtype)
    expert_in = expert_in.at[
        jnp.where(keep, flat_sel, E - 1),
        jnp.where(keep, flat_slot, cap - 1)].add(
            jnp.where(keep[:, None], src, 0).astype(x.dtype),
            mode="drop")

    h = jnp.einsum("ecd,edf->ecf", expert_in, p["wi_gate"])
    h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", expert_in, p["wi_up"])
    expert_out = jnp.einsum("ecf,efd->ecd", h, p["wo"])         # (E, cap, D)

    gathered = expert_out[flat_sel, flat_slot]                  # (T*K, D)
    gathered = jnp.where(keep[:, None], gathered, 0)
    combined = (gathered.reshape(T, K, D)
                * weights[..., None].astype(x.dtype)).sum(axis=1)
    return combined.reshape(B, S, D).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Mamba2 / SSD mixer
# ---------------------------------------------------------------------------

def _ssm_dims(cfg: ModelConfig) -> tuple[int, int, int]:
    d_inner = cfg.ssm_expand * cfg.d_model
    n_heads = d_inner // cfg.ssm_head_dim
    return d_inner, n_heads, cfg.ssm_state


CONV_W = 4  # causal short-conv width


def init_mamba(key, cfg: ModelConfig) -> Params:
    dt = dtype_of(cfg)
    D = cfg.d_model
    d_inner, H, N = _ssm_dims(cfg)
    conv_dim = d_inner + 2 * N
    ks = jax.random.split(key, 8)
    common = {
        "A_log": jnp.zeros((H,), jnp.float32),
        "D_skip": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "out_norm": init_norm(cfg, d_inner),
        "out_proj": _dense_init(ks[2], (d_inner, D), dt),
    }
    return common | {
        "in_proj": _dense_init(ks[0], (D, 2 * d_inner + 2 * N + H), dt),
        "conv_w": _dense_init(ks[1], (CONV_W, conv_dim), dt, scale=0.5),
    }


def _causal_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal conv: x (B,S,C), w (CONV_W, C)."""
    S = x.shape[1]
    x_pad = jnp.pad(x, ((0, 0), (CONV_W - 1, 0), (0, 0)))
    return sum(x_pad[:, i:i + S, :] * w[i][None, None, :]
               for i in range(CONV_W))


def _segsum(x: jax.Array) -> jax.Array:
    """x: (..., Q) log-decays -> (..., Q, Q) lower-tri cumulative sums."""
    Q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((Q, Q), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(xh, dt_h, a_log, Bm, Cm, chunk: int,
                h0: jax.Array | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD scan (Mamba2, arXiv:2405.21060 Sec. 6).

    xh: (B,S,H,P)  dt_h: (B,S,H)  a_log: (H,)  Bm/Cm: (B,S,N).
    Returns (y: (B,S,H,P), final_state: (B,H,P,N)).
    """
    Bsz, S, H, P = xh.shape
    N = Bm.shape[-1]
    nc = S // chunk
    a = -jnp.exp(a_log)                                     # (H,) negative
    da = (dt_h * a[None, None, :]).astype(jnp.float32)      # (B,S,H) log decay
    xw = xh * dt_h[..., None]                               # dt-weighted input

    # reshape into chunks
    def c(t):
        return t.reshape(Bsz, nc, chunk, *t.shape[2:])
    xw_c, da_c, B_c, C_c = c(xw), c(da), c(Bm), c(Cm)

    # intra-chunk (quadratic within chunk)
    L = jnp.exp(_segsum(jnp.moveaxis(da_c, -1, 2)))         # (B,nc,H,Q,Q)
    scores = jnp.einsum("bcqn,bckn->bcqk", C_c, B_c,
                        preferred_element_type=jnp.float32)  # (B,nc,Q,Q)
    y_intra = jnp.einsum("bchqk,bcqk,bckhp->bcqhp", L, scores, xw_c,
                         preferred_element_type=jnp.float32)

    # chunk end-states
    cum = jnp.cumsum(da_c, axis=2)                          # (B,nc,Q,H)
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)         # (B,nc,Q,H)
    states = jnp.einsum("bcqh,bcqn,bcqhp->bchpn", decay_to_end, B_c, xw_c,
                        preferred_element_type=jnp.float32)  # (B,nc,H,P,N)

    # inter-chunk recurrence over nc chunks
    chunk_decay = jnp.exp(cum[:, :, -1, :])                 # (B,nc,H)
    if h0 is None:
        h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)

    def step(h, inp):
        dec, st = inp                                       # (B,H), (B,H,P,N)
        h_new = h * dec[..., None, None] + st
        return h_new, h

    _, h_prev = lax.scan(step,
                         h0,
                         (jnp.moveaxis(chunk_decay, 1, 0),
                          jnp.moveaxis(states, 1, 0)))
    h_prev = jnp.moveaxis(h_prev, 0, 1)                     # (B,nc,H,P,N) state at chunk start
    final = (h_prev[:, -1] * chunk_decay[:, -1, :, None, None]
             + states[:, -1])

    # inter-chunk contribution
    decay_from_start = jnp.exp(cum)                         # (B,nc,Q,H)
    y_inter = jnp.einsum("bcqn,bchpn,bcqh->bcqhp", C_c, h_prev,
                         decay_from_start,
                         preferred_element_type=jnp.float32)
    y = (y_intra + y_inter).reshape(Bsz, S, H, P)
    return y, final


def mamba_fwd(p: Params, cfg: ModelConfig, x: jax.Array,
              return_cache: bool = False):
    """Full-sequence Mamba2 mixer.  x: (B,S,D).  With ``return_cache`` also
    returns the decode cache {ssm, conv} after consuming the sequence."""
    B, S, D = x.shape
    d_inner, H, N = _ssm_dims(cfg)
    zxbcdt = x @ p["in_proj"]
    z, xs, Bm, Cm, dt_r = jnp.split(
        zxbcdt,
        [d_inner, 2 * d_inner, 2 * d_inner + N, 2 * d_inner + 2 * N],
        axis=-1)
    # causal short conv over (x, B, C)
    xbc = jnp.concatenate([xs, Bm, Cm], axis=-1)
    conv = jax.nn.silu(_causal_conv(xbc, p["conv_w"]))
    xs, Bm, Cm = jnp.split(conv, [d_inner, d_inner + N], axis=-1)
    dt_h = jax.nn.softplus(dt_r.astype(jnp.float32) + p["dt_bias"])
    xh = xs.reshape(B, S, H, cfg.ssm_head_dim)
    pad = (-S) % cfg.ssm_chunk
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt_h = jnp.pad(dt_h, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    y, final_state = ssd_chunked(xh, dt_h, p["A_log"], Bm, Cm, cfg.ssm_chunk)
    y = y[:, :S]
    y = y + xh[:, :S] * p["D_skip"][None, None, :, None]
    y = y.reshape(B, S, d_inner).astype(x.dtype)
    y = norm_fwd(p["out_norm"], y) * jax.nn.silu(z)
    out = (y @ p["out_proj"]).astype(x.dtype)
    if return_cache:
        # conv state: last CONV_W-1 *pre-conv* inputs (pre-silu xbc)
        conv_tail = xbc[:, -(CONV_W - 1):, :].astype(dtype_of(cfg))
        return out, {"ssm": final_state, "conv": conv_tail}
    return out


def init_mamba_cache(cfg: ModelConfig, batch: int, dtype=None) -> Params:
    d_inner, H, N = _ssm_dims(cfg)
    conv_dim = d_inner + 2 * N
    return {
        "ssm": jnp.zeros((batch, H, cfg.ssm_head_dim, N), jnp.float32),
        "conv": jnp.zeros((batch, CONV_W - 1, conv_dim),
                          dtype or dtype_of(cfg)),
    }


def mamba_decode(p: Params, cfg: ModelConfig, x: jax.Array, cache: Params
                 ) -> tuple[jax.Array, Params]:
    """Single-token recurrent update.  x: (B,1,D)."""
    B = x.shape[0]
    d_inner, H, N = _ssm_dims(cfg)
    zxbcdt = x[:, 0] @ p["in_proj"]
    z, xs, Bm, Cm, dt_r = jnp.split(
        zxbcdt,
        [d_inner, 2 * d_inner, 2 * d_inner + N, 2 * d_inner + 2 * N],
        axis=-1)
    xbc = jnp.concatenate([xs, Bm, Cm], axis=-1)              # (B, conv_dim)
    conv_hist = jnp.concatenate([cache["conv"],
                                 xbc[:, None, :].astype(cache["conv"].dtype)],
                                axis=1)                       # (B, CONV_W, C)
    conv = jnp.einsum("bwc,wc->bc", conv_hist, p["conv_w"])
    conv = jax.nn.silu(conv)
    xs, Bm, Cm = jnp.split(conv, [d_inner, d_inner + N], axis=-1)
    dt_h = jax.nn.softplus(dt_r.astype(jnp.float32) + p["dt_bias"])  # (B,H)
    a = -jnp.exp(p["A_log"])
    decay = jnp.exp(dt_h * a[None, :])                        # (B,H)
    xh = xs.reshape(B, H, cfg.ssm_head_dim).astype(jnp.float32)
    dBx = jnp.einsum("bh,bn,bhp->bhpn", dt_h, Bm.astype(jnp.float32), xh)
    h = cache["ssm"] * decay[..., None, None] + dBx
    y = jnp.einsum("bn,bhpn->bhp", Cm.astype(jnp.float32), h)
    y = y + xh * p["D_skip"][None, :, None]
    y = y.reshape(B, d_inner).astype(x.dtype)
    y = norm_fwd(p["out_norm"], y) * jax.nn.silu(z)
    out = (y @ p["out_proj"]).astype(x.dtype)[:, None, :]
    return out, {"ssm": h, "conv": conv_hist[:, 1:, :]}
