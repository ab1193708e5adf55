"""Sharded prefill / decode steps, abstract inputs and state specs for
every (architecture x input-shape) combination, and ``build_step``, which
jits one of them (or the GBA train step) for a mesh.

Training programs are built in :mod:`repro.launch.programs`
(``build_programs`` and the factories it wraps); ``build_step`` takes its
train step from there.  That step computes one buffer slot's gradient
(the pod acts as one PS "worker"; the slot's local batch is the
input-shape global batch), decays it by the token-control rule against
the current global step, accumulates into the sharded gradient
accumulator, and applies the optimizer every M-th microstep under
``lax.cond``: Algorithm 2's buffer rendered in TPU SPMD.  Setting
``buffer_size=1, iota=big`` recovers plain synchronous training, which is
exactly the paper's tuning-free switch.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import GBAConfig, InputShape, ModelConfig
from repro.distributed import sharding as S
from repro.distributed.act_sharding import set_act_spec
from repro.launch import programs as _P
from repro.launch.programs import ARCH_ACC_DTYPE, ARCH_OPTIMIZER
from repro.models import transformer as T
from repro.optim import Optimizer, get_optimizer

SDS = jax.ShapeDtypeStruct


# ---------------------------------------------------------------------------
# abstract inputs (deliverable f): ShapeDtypeStruct stand-ins, no allocation
# ---------------------------------------------------------------------------

def model_inputs(cfg: ModelConfig, shape: InputShape) -> dict[str, SDS]:
    """Abstract model inputs for one input shape.  Modality frontends are
    stubs per the carve-out: VLM/audio entries carry precomputed patch /
    frame embeddings of the right shape."""
    B = shape.global_batch
    dt = jnp.dtype(cfg.dtype)
    if shape.kind == "train":
        out = {"tokens": SDS((B, shape.seq_len), jnp.int32),
               "labels": SDS((B, shape.seq_len), jnp.int32)}
    elif shape.kind == "prefill":
        out = {"tokens": SDS((B, shape.seq_len), jnp.int32)}
    else:  # decode: ONE new token against a cache of seq_len
        # frontend embeddings live in the cache ("memory"), not the batch
        return {"tokens": SDS((B, 1), jnp.int32)}
    if cfg.family == "vlm":
        out["image_embeds"] = SDS((B, cfg.num_image_tokens, cfg.d_model), dt)
    if cfg.family == "audio":
        out["frames"] = SDS((B, cfg.encoder_frames, cfg.d_model), dt)
    return out


def abstract_params(cfg: ModelConfig) -> Any:
    return jax.eval_shape(
        functools.partial(T.init_model, cfg=cfg), jax.random.PRNGKey(0))


def abstract_cache(cfg: ModelConfig, batch: int, cache_len: int,
                   memory_len: int = 0) -> Any:
    mem = (SDS((batch, memory_len, cfg.d_model), jnp.dtype(cfg.dtype))
           if memory_len else None)

    def build(m):
        return T.init_cache(cfg, batch, cache_len, memory=m)

    return jax.eval_shape(build, mem) if mem is not None else \
        jax.eval_shape(lambda: T.init_cache(cfg, batch, cache_len))


def _memory_len(cfg: ModelConfig) -> int:
    if cfg.family == "vlm":
        return cfg.num_image_tokens
    if cfg.family == "audio":
        return cfg.encoder_frames
    return 0


def opt_state_specs(optimizer: Optimizer, pspecs: Any) -> Any:
    if optimizer.name == "adam":
        return {"m": pspecs, "v": pspecs, "count": P()}
    if optimizer.name == "adagrad":
        return {"accum": pspecs}
    return {}


def train_state_specs(optimizer: Optimizer, pspecs: Any) -> dict:
    return {
        "params": pspecs,
        "opt": opt_state_specs(optimizer, pspecs),
        "acc": pspecs,
        "micro": P(),
        "gstep": P(),
    }


# ---------------------------------------------------------------------------
# serve steps
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ModelConfig):
    def prefill_step(params, batch):
        memory = batch.get("image_embeds")
        if "frames" in batch:
            memory = T.encode_audio(params, cfg, batch["frames"])
        return T.prefill(params, cfg, batch["tokens"], memory=memory)

    return prefill_step


def make_decode_step(cfg: ModelConfig):
    def decode_step(params, token, cache):
        logits, cache = T.decode_step(params, cfg, token, cache)
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_token, logits, cache

    return decode_step


# ---------------------------------------------------------------------------
# jit assembly per (arch x shape x mesh)
# ---------------------------------------------------------------------------

def build_step(cfg: ModelConfig, shape: InputShape, mesh: Mesh,
               gba: GBAConfig | None = None):
    """Returns (jitted_fn, abstract_args tuple) ready for .lower()."""
    gba = gba or GBAConfig(local_batch=shape.global_batch, buffer_size=8)
    # pin the residual stream to batch-sharded layout (act_sharding docs);
    # long_500k (batch=1) replicates instead
    dp = S.data_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    act_spec = P(dp, None, None) if shape.global_batch % dp_size == 0 \
        else P(None, None, None)
    set_act_spec(NamedSharding(mesh, act_spec))
    pshapes = abstract_params(cfg)
    pspecs = S.param_specs(pshapes, mesh)
    named = lambda tree: jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda s: isinstance(s, P))
    binputs = model_inputs(cfg, shape)
    bspecs = {k: S.batch_partition(mesh, v.shape[0], v.ndim)
              for k, v in binputs.items()}

    if shape.kind == "train":
        opt = get_optimizer(ARCH_OPTIMIZER.get(cfg.name, "adam"), 1e-3)
        acc_dt = ARCH_ACC_DTYPE.get(cfg.name, jnp.float32)
        sspecs = train_state_specs(opt, pspecs)
        state_sds = jax.eval_shape(
            functools.partial(_P.init_train_state, optimizer=opt,
                              acc_dtype=acc_dt), pshapes)
        # donate the state like launch.train does — without this the
        # lowered step double-allocates params + opt + acc
        # (auditor rule GBA-DON-001)
        fn = jax.jit(_P.make_train_step(cfg, opt, gba),
                     in_shardings=(named(sspecs), named(bspecs),
                                   NamedSharding(mesh, P())),
                     out_shardings=(named(sspecs), None),
                     donate_argnums=0)
        return fn, (state_sds, binputs, SDS((), jnp.int32))

    if shape.kind == "prefill":
        cache_sds = abstract_cache(cfg, shape.global_batch, shape.seq_len,
                                   _memory_len(cfg))
        cspecs = S.cache_specs(cache_sds, cfg, mesh, shape.global_batch)
        fn = jax.jit(make_prefill_step(cfg),
                     in_shardings=(named(pspecs), named(bspecs)),
                     out_shardings=(None, named(cspecs)))
        return fn, (pshapes, binputs)

    # decode
    mem_len = _memory_len(cfg)
    cache_sds = abstract_cache(cfg, shape.global_batch, shape.seq_len,
                               mem_len)
    cspecs = S.cache_specs(cache_sds, cfg, mesh, shape.global_batch)
    tok_sds = binputs["tokens"]
    fn = jax.jit(make_decode_step(cfg),
                 in_shardings=(named(pspecs), named(bspecs["tokens"]),
                               named(cspecs)),
                 out_shardings=(named(bspecs["tokens"]), None,
                                named(cspecs)))
    return fn, (pshapes, tok_sds, cache_sds)
