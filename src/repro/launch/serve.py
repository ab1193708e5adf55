"""Serving launcher: batched prefill + decode.

    PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-3b \
        --reduced --batch 8 --prompt-len 32 --gen-len 32

``--engine`` runs the continuous-batching :class:`repro.serving.
ServingEngine` instead of the fixed-batch loop: requests are admitted
into decode slots from a :class:`ParamSource` — frozen init by default,
``--ckpt PATH`` (an npz file or a CheckpointManager directory, newest
step wins) for checkpoint serving:

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-780m \
        --reduced --engine --requests 8 --gen-len 24
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh, make_smoke_mesh
from repro.launch.steps import make_decode_step
from repro.models import transformer as T


def run_engine(args, cfg, mesh) -> None:
    """Continuous-batching serving from a ParamSource."""
    from repro.serving import (Request, ServingConfig, ServingEngine,
                               StaticSource)
    if args.ckpt:
        source = StaticSource.from_checkpoint(args.ckpt,
                                              select=args.ckpt_select or None)
    else:
        source = StaticSource(T.init_model(jax.random.PRNGKey(0), cfg))
    scfg = ServingConfig(num_slots=args.batch,
                         max_len=args.prompt_len + args.gen_len)
    eng = ServingEngine(source, cfg, config=scfg)
    rng = np.random.default_rng(0)
    with mesh:
        for uid in range(args.requests):
            plen = int(rng.integers(4, args.prompt_len + 1))
            eng.submit(Request(
                uid=uid,
                prompt=rng.integers(0, cfg.vocab_size, plen,
                                    dtype=np.int64).astype(np.int32),
                max_new_tokens=args.gen_len))
        stats = eng.run()
    print(f"engine: {stats['completed']} completed in "
          f"{stats['decode_steps']} steps, "
          f"{stats['tokens_per_s']:,.0f} tok/s, slot util "
          f"{stats['slot_utilization']:.2f}, param v{stats['param_version']} "
          f"(step {stats['param_step']}), clamped "
          f"{stats['clamped_requests']}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--engine", action="store_true",
                    help="continuous-batching ServingEngine from a "
                         "ParamSource instead of the fixed-batch loop")
    ap.add_argument("--requests", type=int, default=8,
                    help="requests to submit with --engine")
    ap.add_argument("--ckpt", default="",
                    help="serve params from this checkpoint (npz file or "
                         "CheckpointManager dir) instead of fresh init")
    ap.add_argument("--ckpt-select", default="",
                    help="subtree of the checkpoint holding the params "
                         "(e.g. 'params' for a full train state)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
        mesh = make_smoke_mesh()
    else:
        mesh = make_production_mesh()
    if args.engine:
        run_engine(args, cfg, mesh)
        return
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    key = jax.random.PRNGKey(1)
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    memory = None
    if cfg.family == "vlm":
        memory = jax.random.normal(
            key, (args.batch, cfg.num_image_tokens, cfg.d_model),
            jnp.float32).astype(jnp.dtype(cfg.dtype))
    if cfg.family == "audio":
        frames = jax.random.normal(
            key, (args.batch, cfg.encoder_frames, cfg.d_model),
            jnp.float32).astype(jnp.dtype(cfg.dtype))
        memory = T.encode_audio(params, cfg, frames)

    cache_len = args.prompt_len + args.gen_len
    with mesh:
        t0 = time.perf_counter()
        logits, cache = jax.jit(
            lambda p, t: T.prefill(p, cfg, t, memory=memory,
                                   cache_len=cache_len))(params, prompts)
        jax.block_until_ready(logits)
        print(f"prefill {args.batch}x{args.prompt_len}: "
              f"{(time.perf_counter() - t0) * 1e3:.0f} ms")
        decode = jax.jit(make_decode_step(cfg))
        token = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        t0 = time.perf_counter()
        for _ in range(args.gen_len - 1):
            token, _, cache = decode(params, token, cache)
        jax.block_until_ready(token)
        dt = time.perf_counter() - t0
        print(f"decode {args.gen_len - 1} steps: {dt * 1e3:.0f} ms "
              f"({args.batch * (args.gen_len - 1) / dt:,.0f} tok/s)")


if __name__ == "__main__":
    main()
