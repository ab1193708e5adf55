"""End-to-end serving driver: batched prefill + decode of a small LM.

    PYTHONPATH=src python examples/serve_lm.py [--arch starcoder2-3b]

Uses the REDUCED variant of an assigned architecture (the full configs
need a TPU), serves a batch of 8 requests: prefill the prompts,
then greedy-decode 32 tokens each through the production decode step.
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.launch.steps import make_decode_step
from repro.models import transformer as T


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b", choices=ARCH_IDS)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen-len", type=int, default=32)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    if cfg.family in ("vlm", "audio"):
        raise SystemExit("use a text arch for this demo")
    print(f"serving {cfg.name}: {args.batch} requests, "
          f"prompt {args.prompt_len}, generate {args.gen_len}")
    key = jax.random.PRNGKey(0)
    params = T.init_model(key, cfg)
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)

    cache_len = args.prompt_len + args.gen_len
    t0 = time.perf_counter()
    prefill_jit = jax.jit(lambda p, t: T.prefill(p, cfg, t,
                                                 cache_len=cache_len))
    logits, cache = prefill_jit(params, prompts)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0
    print(f"prefill: {t_prefill * 1e3:.1f} ms "
          f"({args.batch * args.prompt_len / t_prefill:,.0f} tok/s)")

    decode = jax.jit(make_decode_step(cfg))
    token = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    out = [token]
    t0 = time.perf_counter()
    for _ in range(args.gen_len - 1):
        token, _, cache = decode(params, token, cache)
        out.append(token)
    jax.block_until_ready(token)
    t_dec = time.perf_counter() - t0
    gen = jnp.concatenate(out, axis=1)
    print(f"decode: {t_dec * 1e3:.1f} ms "
          f"({args.batch * (args.gen_len - 1) / t_dec:,.0f} tok/s)")
    print("first request's generated ids:", gen[0, :16].tolist(), "...")


if __name__ == "__main__":
    main()
