"""End-to-end LM training driver with first-class GBA.

    PYTHONPATH=src python examples/train_lm.py               # ~25M, quick
    PYTHONPATH=src python examples/train_lm.py --params 100m --steps 300

Builds a granite-family dense decoder at the requested scale, streams the
synthetic LM source, and trains with the GBA train step (M-slot buffer,
token-control decay) — the same step ``launch.steps.build_step`` lowers.
Loss must drop visibly within a few dozen steps.
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.base import GBAConfig
from repro.data import make_lm_stream
from repro.launch.programs import build_programs
from repro.models import transformer as T
from repro.optim import get_optimizer

SIZES = {
    # name: (layers, d_model, heads, kv, d_ff, vocab)
    "25m": (6, 384, 6, 2, 1536, 8192),
    "100m": (12, 768, 12, 4, 3072, 16384),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--params", default="25m", choices=SIZES)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--buffer", type=int, default=4, help="GBA M")
    args = ap.parse_args()

    L, D, H, KV, F, V = SIZES[args.params]
    cfg = dataclasses.replace(
        get_config("granite-8b"), name=f"granite-{args.params}",
        num_layers=L, d_model=D, num_heads=H, num_kv_heads=KV, d_ff=F,
        vocab_size=V, dtype="float32")
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    n = T.param_count(params)
    print(f"model: {cfg.name}  {n / 1e6:.1f}M params  "
          f"GBA buffer M={args.buffer}")

    stream = make_lm_stream(V, args.seq, args.batch, seed=0)
    opt = get_optimizer("adam", 3e-4)
    gba = GBAConfig(local_batch=args.batch, buffer_size=args.buffer,
                    staleness_tolerance=4)
    progs = build_programs(cfg, gba, mode="pytree", params=params,
                           optimizer=opt, acc_dtype=jnp.float32)
    step_fn, state = progs.step, progs.state

    t0 = time.perf_counter()
    first = None
    for i in range(args.steps):
        batch = stream.batch(i)
        token = jnp.asarray(i // args.buffer, jnp.int32)  # fresh tokens
        state, loss = step_fn(
            state, {"tokens": jnp.asarray(batch["tokens"]),
                    "labels": jnp.asarray(batch["labels"])}, token)
        loss = float(loss)
        first = first if first is not None else loss
        if i % 10 == 0 or i == args.steps - 1:
            dt = time.perf_counter() - t0
            tput = args.batch * args.seq * (i + 1) / dt
            print(f"step {i:4d}  micro-loss {loss:.4f}  "
                  f"gstep {int(state['gstep'])}  {tput:,.0f} tok/s")
    print(f"\nloss: {first:.4f} -> {loss:.4f} "
          f"({'improved' if loss < first else 'NO IMPROVEMENT'})")


if __name__ == "__main__":
    main()
