"""Model assembly: embedding -> [prefix layers] -> scan(pattern blocks) ->
norm -> logits, for every assigned architecture family.

The repeated part of the stack runs under ``lax.scan`` over params stacked on
a leading ``num_repeats`` axis, which keeps the lowered HLO compact: its
size does not grow with depth.

Zamba2's *shared* attention block is faithful to the model card: a single
set of attention params applied inside every ``mamba_attn`` layer (passed to
the scan body by closure, not stacked).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.distributed.act_sharding import constrain
from repro.models import layers as L

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# per-kind layer init / apply
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ModelConfig, kind: str) -> Params:
    ks = jax.random.split(key, 6)
    if kind in ("global", "local"):
        return {"ln1": L.init_norm(cfg), "attn": L.init_attention(ks[0], cfg),
                "ln2": L.init_norm(cfg), "mlp": L.init_mlp(ks[1], cfg)}
    if kind in ("moe", "local_moe"):
        return {"ln1": L.init_norm(cfg), "attn": L.init_attention(ks[0], cfg),
                "ln2": L.init_norm(cfg), "moe": L.init_moe(ks[1], cfg)}
    if kind == "cross":
        return {"ln1": L.init_norm(cfg), "attn": L.init_attention(ks[0], cfg),
                "lnx": L.init_norm(cfg),
                "xattn": L.init_attention(ks[1], cfg, cross=True),
                "ln2": L.init_norm(cfg), "mlp": L.init_mlp(ks[2], cfg)}
    if kind == "mamba":
        return {"ln1": L.init_norm(cfg), "mixer": L.init_mamba(ks[0], cfg)}
    if kind == "mamba_attn":
        # shared-attention params are global (see init_model); the per-layer
        # part is just the mamba mixer + norms
        return {"ln1": L.init_norm(cfg), "mixer": L.init_mamba(ks[0], cfg),
                "ln_sh": L.init_norm(cfg)}
    raise ValueError(kind)


def _layer_fwd(p: Params, cfg: ModelConfig, kind: str, x, positions,
               *, memory=None, shared_attn=None, aux=0.0):
    window = cfg.sliding_window if kind in ("local", "local_moe") else 0
    if kind in ("global", "local"):
        x = x + L.attention_fwd(p["attn"], cfg, L.norm_fwd(p["ln1"], x),
                                positions, window=window)
        x = x + L.mlp_fwd(p["mlp"], L.norm_fwd(p["ln2"], x))
    elif kind in ("moe", "local_moe"):
        x = x + L.attention_fwd(p["attn"], cfg, L.norm_fwd(p["ln1"], x),
                                positions, window=window)
        h, a = L.moe_fwd(p["moe"], cfg, L.norm_fwd(p["ln2"], x))
        x, aux = x + h, aux + a
    elif kind == "cross":
        x = x + L.attention_fwd(p["attn"], cfg, L.norm_fwd(p["ln1"], x),
                                positions)
        x = x + L.attention_fwd(p["xattn"], cfg, L.norm_fwd(p["lnx"], x),
                                positions, kv_override=memory)
        x = x + L.mlp_fwd(p["mlp"], L.norm_fwd(p["ln2"], x))
    elif kind == "mamba":
        x = x + L.mamba_fwd(p["mixer"], cfg, L.norm_fwd(p["ln1"], x))
    elif kind == "mamba_attn":
        x = x + L.mamba_fwd(p["mixer"], cfg, L.norm_fwd(p["ln1"], x))
        x = x + L.attention_fwd(shared_attn["attn"], cfg,
                                L.norm_fwd(p["ln_sh"], x), positions)
    else:
        raise ValueError(kind)
    return x, aux


def _layer_cache(cfg: ModelConfig, kind: str, batch: int, cache_len: int
                 ) -> Params:
    window = cfg.sliding_window if kind in ("local", "local_moe") else 0
    if kind in ("global", "local", "moe", "local_moe"):
        return {"attn": L.init_attn_cache(cfg, batch, cache_len, window)}
    if kind == "cross":
        return {"attn": L.init_attn_cache(cfg, batch, cache_len)}
    if kind == "mamba":
        return {"ssm": L.init_mamba_cache(cfg, batch)}
    if kind == "mamba_attn":
        return {"ssm": L.init_mamba_cache(cfg, batch),
                "attn": L.init_attn_cache(cfg, batch, cache_len)}
    raise ValueError(kind)


def _layer_decode(p: Params, cfg: ModelConfig, kind: str, x, cache, pos,
                  *, memory=None, shared_attn=None):
    window = cfg.sliding_window if kind in ("local", "local_moe") else 0
    new = dict(cache)
    if kind in ("global", "local"):
        h, new["attn"] = L.attention_decode(
            p["attn"], cfg, L.norm_fwd(p["ln1"], x), cache["attn"], pos,
            window=window)
        x = x + h
        x = x + L.mlp_fwd(p["mlp"], L.norm_fwd(p["ln2"], x))
    elif kind in ("moe", "local_moe"):
        h, new["attn"] = L.attention_decode(
            p["attn"], cfg, L.norm_fwd(p["ln1"], x), cache["attn"], pos,
            window=window)
        x = x + h
        h, _ = L.moe_fwd(p["moe"], cfg, L.norm_fwd(p["ln2"], x))
        x = x + h
    elif kind == "cross":
        h, new["attn"] = L.attention_decode(
            p["attn"], cfg, L.norm_fwd(p["ln1"], x), cache["attn"], pos)
        x = x + h
        h, _ = L.attention_decode(p["xattn"], cfg, L.norm_fwd(p["lnx"], x),
                                  cache["attn"], pos, kv_override=memory)
        x = x + h
        x = x + L.mlp_fwd(p["mlp"], L.norm_fwd(p["ln2"], x))
    elif kind == "mamba":
        h, new["ssm"] = L.mamba_decode(p["mixer"], cfg,
                                       L.norm_fwd(p["ln1"], x), cache["ssm"])
        x = x + h
    elif kind == "mamba_attn":
        h, new["ssm"] = L.mamba_decode(p["mixer"], cfg,
                                       L.norm_fwd(p["ln1"], x), cache["ssm"])
        x = x + h
        h, new["attn"] = L.attention_decode(
            shared_attn["attn"], cfg, L.norm_fwd(p["ln_sh"], x),
            cache["attn"], pos)
        x = x + h
    else:
        raise ValueError(kind)
    return x, new


# ---------------------------------------------------------------------------
# model init
# ---------------------------------------------------------------------------

def init_model(key, cfg: ModelConfig) -> Params:
    dt = L.dtype_of(cfg)
    keys = jax.random.split(key, 8)
    params: Params = {
        "embed": L._dense_init(keys[0], (cfg.vocab_size, cfg.d_model), dt,
                               scale=0.02),
        "final_norm": L.init_norm(cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L._dense_init(
            keys[1], (cfg.d_model, cfg.vocab_size), dt)
    # un-scanned prefix layers
    if cfg.prefix_layers:
        pk = jax.random.split(keys[2], len(cfg.prefix_layers))
        params["prefix"] = [
            _init_layer(pk[i], cfg, kind)
            for i, kind in enumerate(cfg.prefix_layers)]
    # scanned pattern blocks: stack params over num_repeats
    n_rep = cfg.num_repeats

    def init_block(k):
        bk = jax.random.split(k, len(cfg.block_pattern))
        return {f"l{i}": _init_layer(bk[i], cfg, kind)
                for i, kind in enumerate(cfg.block_pattern)}

    params["blocks"] = jax.vmap(init_block)(jax.random.split(keys[3], n_rep))
    # shared attention (zamba2-style)
    if "mamba_attn" in cfg.block_pattern:
        params["shared_attn"] = {"attn": L.init_attention(keys[4], cfg)}
    # audio: encoder stack (self-attention only), scanned
    if cfg.encoder_layers:
        def init_enc(k):
            return _init_layer(k, cfg, "global")
        params["encoder"] = jax.vmap(init_enc)(
            jax.random.split(keys[5], cfg.encoder_layers))
        params["enc_norm"] = L.init_norm(cfg)
    return params


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def encode_audio(params: Params, cfg: ModelConfig, frames: jax.Array
                 ) -> jax.Array:
    """Run the (stub-fed) encoder: frames (B, T, D) -> memory (B, T, D)."""
    B, T, _ = frames.shape
    positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))

    def body(x, p):
        x, _ = _layer_fwd(p, cfg, "global", x, positions)
        return x, None

    x, _ = lax.scan(body, frames.astype(L.dtype_of(cfg)), params["encoder"])
    return L.norm_fwd(params["enc_norm"], x)


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
            memory: jax.Array | None = None) -> tuple[jax.Array, jax.Array]:
    """Full-sequence forward.  tokens: (B,S) int32.  memory: cross-attn
    context (image patch embeds / encoder output).  Returns (logits, aux)."""
    B, S = tokens.shape
    x = params["embed"][tokens] * (cfg.d_model ** 0.5 if cfg.tie_embeddings
                                   else 1.0)
    x = constrain(x.astype(L.dtype_of(cfg)))
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    aux = jnp.zeros((), jnp.float32)
    shared = params.get("shared_attn")

    for i, kind in enumerate(cfg.prefix_layers):
        x, aux = _layer_fwd(params["prefix"][i], cfg, kind, x, positions,
                            memory=memory, shared_attn=shared, aux=aux)

    def body(carry, block_p):
        x, aux = carry
        for i, kind in enumerate(cfg.block_pattern):
            x, aux = _layer_fwd(block_p[f"l{i}"], cfg, kind, x, positions,
                                memory=memory, shared_attn=shared, aux=aux)
            x = constrain(x)
        return (x, aux), None

    (x, aux), _ = lax.scan(body, (x, aux), params["blocks"])
    x = L.norm_fwd(params["final_norm"], x)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = jnp.einsum("bsd,dv->bsv", x, head,
                        preferred_element_type=jnp.float32)
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits, aux


# ---------------------------------------------------------------------------
# prefill: forward + cache construction (what prefill_32k lowers)
# ---------------------------------------------------------------------------

def _layer_prefill(p: Params, cfg: ModelConfig, kind: str, x, positions,
                   seq_len: int, cache_len: int, *, memory=None,
                   shared_attn=None):
    window = cfg.sliding_window if kind in ("local", "local_moe") else 0
    cache: Params = {}
    if kind in ("global", "local", "moe", "local_moe"):
        h, (k, v) = L.attention_fwd(p["attn"], cfg, L.norm_fwd(p["ln1"], x),
                                    positions, window=window, return_kv=True)
        cache["attn"] = L.kv_to_cache(cfg, k, v, seq_len, cache_len,
                                      window)
        x = x + h
        if kind in ("moe", "local_moe"):
            h, _ = L.moe_fwd(p["moe"], cfg, L.norm_fwd(p["ln2"], x))
        else:
            h = L.mlp_fwd(p["mlp"], L.norm_fwd(p["ln2"], x))
        x = x + h
    elif kind == "cross":
        h, (k, v) = L.attention_fwd(p["attn"], cfg, L.norm_fwd(p["ln1"], x),
                                    positions, return_kv=True)
        cache["attn"] = L.kv_to_cache(cfg, k, v, seq_len, cache_len)
        x = x + h
        x = x + L.attention_fwd(p["xattn"], cfg, L.norm_fwd(p["lnx"], x),
                                positions, kv_override=memory)
        x = x + L.mlp_fwd(p["mlp"], L.norm_fwd(p["ln2"], x))
    elif kind == "mamba":
        h, cache["ssm"] = L.mamba_fwd(p["mixer"], cfg,
                                      L.norm_fwd(p["ln1"], x),
                                      return_cache=True)
        x = x + h
    elif kind == "mamba_attn":
        h, cache["ssm"] = L.mamba_fwd(p["mixer"], cfg,
                                      L.norm_fwd(p["ln1"], x),
                                      return_cache=True)
        x = x + h
        h, (k, v) = L.attention_fwd(shared_attn["attn"], cfg,
                                    L.norm_fwd(p["ln_sh"], x), positions,
                                    return_kv=True)
        cache["attn"] = L.kv_to_cache(cfg, k, v, seq_len, cache_len)
        x = x + h
    else:
        raise ValueError(kind)
    return x, cache


def prefill(params: Params, cfg: ModelConfig, tokens: jax.Array,
            memory: jax.Array | None = None,
            cache_len: int | None = None) -> tuple[jax.Array, Params]:
    """Score the prompt and build the decode cache.  Returns (last-position
    logits (B,V), cache ready for decode_step at pos=S)."""
    B, S = tokens.shape
    cache_len = cache_len or S
    x = params["embed"][tokens] * (cfg.d_model ** 0.5 if cfg.tie_embeddings
                                   else 1.0)
    x = x.astype(L.dtype_of(cfg))
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    shared = params.get("shared_attn")
    cache: Params = {"pos": jnp.full((), S, jnp.int32)}
    if memory is not None:
        cache["memory"] = memory

    if cfg.prefix_layers:
        pc = []
        for i, kind in enumerate(cfg.prefix_layers):
            x, c = _layer_prefill(params["prefix"][i], cfg, kind, x,
                                  positions, S, cache_len, memory=memory,
                                  shared_attn=shared)
            pc.append(c)
        cache["prefix"] = pc

    def body(x, block_p):
        block_c = {}
        for i, kind in enumerate(cfg.block_pattern):
            x, block_c[f"l{i}"] = _layer_prefill(
                block_p[f"l{i}"], cfg, kind, x, positions, S, cache_len,
                memory=memory, shared_attn=shared)
            x = constrain(x)
        return x, block_c

    x, blocks_c = lax.scan(body, x, params["blocks"])
    cache["blocks"] = blocks_c
    x = L.norm_fwd(params["final_norm"], x[:, -1:, :])
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = jnp.einsum("bsd,dv->bsv", x, head,
                        preferred_element_type=jnp.float32)[:, 0]
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits, cache


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               memory: jax.Array | None = None) -> Params:
    cache: Params = {"pos": jnp.zeros((), jnp.int32)}
    if cfg.prefix_layers:
        cache["prefix"] = [
            _layer_cache(cfg, kind, batch, cache_len)
            for kind in cfg.prefix_layers]

    def one_block(_):
        return {f"l{i}": _layer_cache(cfg, kind, batch, cache_len)
                for i, kind in enumerate(cfg.block_pattern)}

    cache["blocks"] = jax.vmap(one_block)(jnp.arange(cfg.num_repeats))
    if memory is not None:
        cache["memory"] = memory
    return cache


def decode_step(params: Params, cfg: ModelConfig, token: jax.Array,
                cache: Params) -> tuple[jax.Array, Params]:
    """token: (B,1) int32 -> (logits (B,1,V), new cache)."""
    B = token.shape[0]
    pos = cache["pos"]
    memory = cache.get("memory")
    shared = params.get("shared_attn")
    x = params["embed"][token] * (cfg.d_model ** 0.5 if cfg.tie_embeddings
                                  else 1.0)
    x = x.astype(L.dtype_of(cfg))
    new_cache = dict(cache)
    new_cache["pos"] = pos + 1

    if cfg.prefix_layers:
        new_prefix = []
        for i, kind in enumerate(cfg.prefix_layers):
            x, c = _layer_decode(params["prefix"][i], cfg, kind, x,
                                 cache["prefix"][i], pos, memory=memory,
                                 shared_attn=shared)
            new_prefix.append(c)
        new_cache["prefix"] = new_prefix

    def body(x, xs):
        block_p, block_c = xs
        new_c = {}
        for i, kind in enumerate(cfg.block_pattern):
            x, new_c[f"l{i}"] = _layer_decode(
                block_p[f"l{i}"], cfg, kind, x, block_c[f"l{i}"], pos,
                memory=memory, shared_attn=shared)
        return x, new_c

    x, new_blocks = lax.scan(body, x, (params["blocks"], cache["blocks"]))
    new_cache["blocks"] = new_blocks
    x = L.norm_fwd(params["final_norm"], x)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = jnp.einsum("bsd,dv->bsv", x, head,
                        preferred_element_type=jnp.float32)
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits, new_cache


# ---------------------------------------------------------------------------
# losses / steps (undistributed reference; sharded versions in launch/)
# ---------------------------------------------------------------------------

def lm_loss(params: Params, cfg: ModelConfig, tokens: jax.Array,
            labels: jax.Array, memory: jax.Array | None = None) -> jax.Array:
    logits, aux = forward(params, cfg, tokens, memory=memory)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(nll) + cfg.router_aux_loss_weight * aux


def param_count(params: Params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# layer groups: how the sharded fused-PS step consumes these params
# ---------------------------------------------------------------------------

def param_group_key(path_names: tuple[str, ...]) -> str:
    """Canonical layer-group for a param pytree path (the grouping the
    layer-grouped ``ShardedFlatLayout`` / ``make_gba_fused_psum_step``
    use).  Groups follow the order the forward consumes params — embed,
    then each prefix layer, then each scanned block-pattern position (one
    group per ``l{i}``, its leaves stacked over ``num_repeats``), then the
    tail norms/head — so a just-in-time per-group ``all_gather`` never
    holds more than one group's worth of gathered params live at once.

    Path grammar (see :func:`init_model`): top-level keys ``embed``,
    ``lm_head``, ``final_norm``, ``prefix`` (list), ``blocks`` (dict of
    ``l{i}``), ``shared_attn``, ``encoder``, ``enc_norm``.
    """
    if not path_names:
        return "misc"
    head = path_names[0]
    if head == "blocks" and len(path_names) > 1:
        return f"blocks.{path_names[1]}"       # one group per pattern slot
    if head == "prefix" and len(path_names) > 1:
        return f"prefix.{path_names[1]}"       # one group per prefix layer
    if head == "lm_head":
        return "head"
    # embed, final_norm, shared_attn, encoder, enc_norm, ...: one group per
    # top-level module (norms are tiny; their groups pad to one tile/shard)
    return head
