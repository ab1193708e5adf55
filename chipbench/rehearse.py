#!/usr/bin/env python3
"""Compile rehearsal of the recsys cells' replay steps for a described
TPU v5e, without the chip, and the memory each would hold.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py deepfm-criteo gba_strained \
        [--capacity 500000 ...]

For each table size it compiles every step variant the traffic uses
(``shared_src`` true and false for GBA, true for sync) through the
program's ``GBATrainer`` at the cell's real shapes, and prints
``memory_analysis``.  The resident estimate adds what lives outside the
step program: the version ring (``history`` parameter copies), Adam's two
moments, ``last_update``, and the stacked versions an unshared step takes.
It bounds a configuration's ``hash_capacity`` before a chip run; the
chip's own memory statistics set it (PERF.md).  A compile is not a chip
run: it says nothing about time.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def sds(tree, sharding, lead=None):
    import jax
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            ((lead,) if lead else ()) + tuple(x.shape), x.dtype,
            sharding=sharding), tree)


def rehearse(cfg: dict, traffic: dict, sharding) -> dict:
    import jax
    import jax.numpy as jnp
    from chipbench.runners import recsys_replay as R
    from chipbench.reference import model_module
    from repro.kernels import runtime

    runtime.set_interpret(False)
    trainer, optimizer = R.make_trainer(cfg, traffic)
    mod = model_module(cfg)
    params = jax.eval_shape(lambda k: mod.init(k, cfg),
                            jax.random.PRNGKey(0))
    opt = jax.eval_shape(optimizer.init, params)
    m, lb = R.slots_per_step(traffic), traffic["local_batch"]
    batch = {"fields": jnp.zeros((m, lb, cfg["num_fields"]), jnp.int32),
             "label": jnp.zeros((m, lb), jnp.float32)}
    if cfg["behavior_len"]:
        batch["behavior"] = jnp.zeros((m, lb, cfg["behavior_len"]),
                                      jnp.int32)
        batch["target"] = jnp.zeros((m, lb), jnp.int32)
    i32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                             sharding=sharding)
    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                             sharding=sharding)
    param_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(params))
    variants = [True, False] if traffic["mode"] == "gba" else [True]
    out = {"param_bytes": param_bytes, "variants": {}}
    for shared in variants:
        step = trainer._make_step(traffic["mode"] == "gba", m, shared)
        src = sds(params, sharding, None if shared else m)
        args = (src, sds(params, sharding), sds(opt, sharding),
                sds(batch, sharding), i32((m,)), f32((m,)), i32(()),
                i32((cfg["hash_capacity"],)))
        ma = step.lower(*args).compile().memory_analysis()
        out["variants"][f"shared_src={shared}"] = {
            "argument": ma.argument_size_in_bytes,
            "output": ma.output_size_in_bytes,
            "temp": ma.temp_size_in_bytes}
    worst = max(v["output"] + v["temp"] + v["argument"]
                for v in out["variants"].values())
    # the step's arguments hold the current params, Adam and the stacked
    # source; outside it live the other history - 1 ring versions
    out["resident_estimate"] = worst + (cfg["history"] - 1) * param_bytes
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("traffic")
    ap.add_argument("--capacity", type=int, nargs="*")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    with open(ROOT / "chipbench" / "configs" / f"{args.config}.json") as f:
        cfg = json.load(f)
    with open(ROOT / "chipbench" / "traffic" / f"{args.traffic}.json") as f:
        traffic = json.load(f)
    for cap in args.capacity or [cfg["hash_capacity"]]:
        out = rehearse(dict(cfg, hash_capacity=cap), traffic, one_chip)
        print(json.dumps({"config": args.config, "traffic": args.traffic,
                          "hash_capacity": cap, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
