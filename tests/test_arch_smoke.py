"""Per-architecture smoke tests (deliverable f): REDUCED variant of each
family runs one forward + one train step + one decode step on CPU; output
shapes asserted, no NaNs."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import GBAConfig, InputShape
from repro.launch.programs import init_train_state, make_train_step
from repro.launch.steps import model_inputs
from repro.models import layers as L
from repro.models import transformer as T
from repro.optim import get_optimizer

B, S = 2, 32


def _memory_for(cfg, key):
    if cfg.family == "vlm":
        return jax.random.normal(
            key, (B, cfg.num_image_tokens, cfg.d_model), jnp.float32)
    return None


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_decode(arch):
    cfg = get_config(arch).reduced()
    key = jax.random.PRNGKey(0)
    params = T.init_model(key, cfg)
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    memory = _memory_for(cfg, key)
    if cfg.family == "audio":
        frames = jax.random.normal(
            key, (B, cfg.encoder_frames, cfg.d_model), jnp.float32)
        memory = T.encode_audio(params, cfg, frames)
        assert not jnp.isnan(memory).any()
    logits, aux = T.forward(params, cfg, toks, memory=memory)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert not jnp.isnan(logits).any()
    assert jnp.isfinite(aux)
    cache = T.init_cache(cfg, B, S + 4, memory=memory)
    lg, cache2 = T.decode_step(params, cfg, toks[:, :1], cache)
    assert lg.shape == (B, 1, cfg.vocab_size)
    assert not jnp.isnan(lg).any()
    assert int(cache2["pos"]) == 1


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step(arch):
    """One GBA train step on the reduced config: loss finite, params move."""
    cfg = get_config(arch).reduced()
    key = jax.random.PRNGKey(1)
    params = T.init_model(key, cfg)
    opt = get_optimizer("adam", 1e-3)
    gba = GBAConfig(local_batch=B, buffer_size=1, staleness_tolerance=4)
    step_fn = jax.jit(make_train_step(cfg, opt, gba))
    state = init_train_state(params, opt)
    shape = InputShape("smoke", S, B, "train")
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
             "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
    if cfg.family == "vlm":
        batch["image_embeds"] = jax.random.normal(
            key, (B, cfg.num_image_tokens, cfg.d_model), jnp.float32
        ).astype(jnp.dtype(cfg.dtype))
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            key, (B, cfg.encoder_frames, cfg.d_model), jnp.float32
        ).astype(jnp.dtype(cfg.dtype))
    state2, loss = step_fn(state, batch, jnp.zeros((), jnp.int32))
    assert jnp.isfinite(loss), (arch, loss)
    # buffer_size=1 -> apply happened; embed must have moved
    moved = jnp.abs(state2["params"]["embed"] - params["embed"]).max()
    assert moved > 0
    assert int(state2["gstep"]) == 1


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_model_inputs_shapes(arch):
    cfg = get_config(arch)
    tr = model_inputs(cfg, InputShape("train_4k", 4096, 256, "train"))
    assert tr["tokens"].shape == (256, 4096)
    dec = model_inputs(cfg, InputShape("decode_32k", 32768, 128, "decode"))
    assert dec["tokens"].shape == (128, 1)
    assert "frames" not in dec and "image_embeds" not in dec


def _reference_attention(p, cfg, x):
    """Causal softmax attention written out per query head in float32:
    RoPE on q and k, each kv head repeated for its query group, an
    explicit (S, S) mask, the optional tanh score cap."""
    _, S, _ = x.shape
    hd = cfg.resolved_head_dim
    half = hd // 2
    group = cfg.num_heads // cfg.num_kv_heads
    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"])
    k = jnp.einsum("bsd,dnh->bsnh", x, p["wk"])
    v = jnp.einsum("bsd,dnh->bsnh", x, p["wv"])
    inv_freq = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32)
                                  / half)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]

    def rotate(t):
        t1, t2 = t[..., :half], t[..., half:]
        return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin],
                               axis=-1)

    q, k = rotate(q), rotate(k)
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bsnh,btnh->bnst", q, k) / math.sqrt(hd)
    if cfg.attn_softcap:
        scores = cfg.attn_softcap * jnp.tanh(scores / cfg.attn_softcap)
    row = jnp.arange(S)[:, None]
    col = jnp.arange(S)[None, :]
    mask = col <= row
    if cfg.sliding_window:
        mask &= row - col < cfg.sliding_window
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bnst,btnh->bsnh", probs, v)
    return jnp.einsum("bsnh,nhd->bsd", out, p["wo"])


@pytest.mark.parametrize("arch,window,softcap", [
    ("granite-8b", 0, 0.0),
    ("gemma2-27b", 8, 0.0),
    ("gemma2-27b", 0, 50.0),
], ids=["causal", "sliding_window", "softcap"])
def test_attention_matches_reference(arch, window, softcap):
    """``layers.attention_fwd`` (the one attention path) equals a plain
    softmax attention with an explicit mask, in float32.  The input is
    scaled so that scores reach the softcap's range; the window and the
    cap each move the reference by far more than the tolerance."""
    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="float32",
                              sliding_window=window, attn_softcap=softcap)
    kp, kx = jax.random.split(jax.random.PRNGKey(3))
    p = L.init_attention(kp, cfg)
    x = 8.0 * jax.random.normal(kx, (B, S, cfg.d_model), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    with jax.default_matmul_precision("highest"):
        got = L.attention_fwd(p, cfg, x, positions, window=window)
        want = _reference_attention(p, cfg, x)
        plain = _reference_attention(
            p, dataclasses.replace(cfg, sliding_window=0, attn_softcap=0.0),
            x)
    atol = 1e-5 * float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=atol)
    if window or softcap:
        assert float(jnp.abs(want - plain).max()) > 100 * atol
