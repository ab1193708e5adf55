"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch granite-8b \
        --steps 20 --reduced [--buffer 8] [--iota 4]

On real TPU hardware this launches the sharded GBA train step on the
production mesh; on a CPU use ``--reduced`` (smoke variant, 1-device
mesh).

``--vocab N`` runs the sparse-module smoke instead: N-row hashed embedding
table trained through the DMA-streamed pooled-lookup kernels on the smoke
mesh.  ``--vocab 1000000`` exercises a table ~250x larger than a VMEM bank
without ever materializing a (V, D) VMEM block (the streamed pipeline
holds O(block) scratch; see repro.kernels.embedding_bag).

    PYTHONPATH=src python -m repro.launch.train --vocab 1000000 --steps 5 \
        [--embed-dim 16] [--block-v 512] [--block-d 128] [--chunk-e 256]

``--mesh DxT`` (e.g. ``--mesh 4x1``) trains on an explicit (data, model)
mesh instead of the smoke/production default; with ``--fused`` the GBA
state uses the sharding-aware flat layout — buffer columns sliced across
the ``data`` axis, ONE ``gba_apply`` launch per PS shard per global step
(core.flat_sharded).  ``--layer-groups`` (default on for ``--fused`` with
a multi-device ``--mesh``) makes that layout layer-grouped under the
model's canonical grouping, so the grouped collective schedule
(core.gba_shard_map) gathers one layer group at a time — per-device peak
gathered bytes is the largest group, not the whole flat vector.  On CPU, pair it with ``--host-devices N`` to force
N host-platform devices (sets ``--xla_force_host_platform_device_count``
before jax device init — the same path the shard_map tests use):

    PYTHONPATH=src python -m repro.launch.train --arch kimi-k2-1t-a32b \
        --reduced --fused --mesh 4x1 --host-devices 4 --steps 8

``--compress {int8,onebit}`` (with ``--fused`` and a multi-device data
axis) switches to the worker-parallel fused-psum loop with a quantized
routing wire (core.gba_shard_map + core.compression): f32 warmup for
``--compress-warmup`` global steps, then int8 payload + per-tile f32
sideband with per-shard error feedback (~0.25x wire bytes).  Sync /
single-device runs auto-fall back to ``none``:

    PYTHONPATH=src python -m repro.launch.train --arch mamba2-780m \
        --reduced --fused --mesh 4x1 --host-devices 4 --steps 8 \
        --compress int8 --compress-warmup 2

``--autoswitch`` (with a multi-device data axis) hands the run to the
end-to-end switching harness (launch.switch_driver): the REAL compiled
sync (pytree psum + Adagrad) and async (token-controlled fused-psum)
steps for this arch run under a ``--plan`` fault plan (quiet|strained),
an AutoSwitchController decides the mode from live per-worker rates, and
the sync<->async swaps carry the flat params/accum across bit-exactly:

    PYTHONPATH=src python -m repro.launch.train --arch mamba2-780m \
        --reduced --mesh 4x1 --host-devices 4 --autoswitch \
        --plan strained --batches 120
"""
from __future__ import annotations

import argparse
import os
import sys
import time

# --host-devices must land in XLA_FLAGS before ANY jax backend init, and
# the repro imports below create arrays at import time — so peek at argv
# here instead of waiting for argparse (both --host-devices N and
# --host-devices=N forms; a malformed value is left for argparse to
# report)
def _peek_host_devices(argv: list[str]) -> str | None:
    for i, a in enumerate(argv):
        if a == "--host-devices" and i + 1 < len(argv):
            return argv[i + 1]
        if a.startswith("--host-devices="):
            return a.split("=", 1)[1]
    return None


_n = _peek_host_devices(sys.argv)
if _n and _n.isdigit():
    os.environ["XLA_FLAGS"] = (
        f"{os.environ.get('XLA_FLAGS', '')} "
        f"--xla_force_host_platform_device_count={_n}").strip()

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import GBAConfig
from repro.data import make_lm_stream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import (make_mesh, make_production_mesh,
                               make_smoke_mesh)
from repro.launch.programs import ARCH_OPTIMIZER, build_programs
from repro.models import transformer as T
from repro.optim import get_optimizer


def run_embedding_smoke(args) -> jax.Array:
    """Sparse-module smoke: a --vocab-row hashed table trained end-to-end
    through the streamed pooled-lookup kernels (forward tile stream +
    sorted-scatter backward) on the smoke mesh.  The (V, D) table lives in
    HBM for both passes; VMEM holds only the double-buffered blocks.
    Returns the trained table."""
    from repro import embeddings
    from repro.kernels.embedding_bag import (BLOCK_D, BLOCK_V, CHUNK_E,
                                             stream_vmem_bytes)
    cap, dim, f = args.vocab, args.embed_dim, 26
    stream = embeddings.StreamConfig(
        block_v=args.block_v or None, block_d=args.block_d or None,
        chunk_e=args.chunk_e or None)
    vm = stream_vmem_bytes(dim, block_v=stream.block_v or BLOCK_V,
                           block_d=stream.block_d or BLOCK_D,
                           chunk_e=stream.chunk_e or CHUNK_E)
    mesh = make_smoke_mesh()
    tbl = embeddings.init_table(jax.random.PRNGKey(0), cap, dim)
    print(f"embedding smoke: V={cap:,} D={dim} "
          f"table={cap * dim * 4 / 1e6:.0f}MB HBM-resident; "
          f"streamed VMEM fwd={vm['fwd']:,}B bwd={vm['bwd']:,}B "
          f"(block-bounded, V-independent)")

    def loss_fn(table_arr, ids, labels):
        pooled = embeddings.pooled_lookup(
            embeddings.EmbeddingTable(table_arr, tbl.last_update), ids,
            stream=stream)
        logit = pooled.sum(axis=-1)
        return jnp.mean(jnp.maximum(logit, 0) - logit * labels
                        + jnp.log1p(jnp.exp(-jnp.abs(logit))))

    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    table_arr = tbl.table
    t0 = time.perf_counter()
    with mesh:
        for i in range(args.steps):
            key = jax.random.PRNGKey(1000 + i)
            raw = jax.random.randint(key, (args.batch, f), 0, 1 << 30)
            ids = embeddings.hash_ids(raw, cap)
            labels = (jax.random.uniform(key, (args.batch,)) < 0.5
                      ).astype(jnp.float32)
            loss, gtable = grad_fn(table_arr, ids, labels)
            table_arr = table_arr - args.lr * gtable
            rate = (i + 1) * args.batch * f / (time.perf_counter() - t0)
            print(f"step {i:4d}  loss {float(loss):.4f}  "
                  f"{rate:,.0f} lookups/s")
    assert jnp.isfinite(loss), "embedding smoke diverged"
    return table_arr


def run_wire_train(args, cfg, mesh, gba, stream, params,
                   scheme: str) -> None:
    """Worker-parallel fused-psum loop with the quantized wire: every
    device along ``data`` is its own PS worker AND shard
    (core.gba_shard_map), gradients route worker->shard per layer group,
    and past ``--compress-warmup`` global steps the routing payload is
    int8 (+ per-tile f32 sideband) with per-shard error feedback.  The
    warmup->compressed switch is a re-jit: two separate jitted programs,
    each with exactly one wire dtype (auditor rule GBA-COLL-005)."""
    from repro.core.compression import CompressionPolicy
    m = mesh.shape["data"]
    pol = CompressionPolicy(scheme=scheme,
                            warmup_steps=args.compress_warmup)
    progs = build_programs(cfg, gba, mode="wire", params=params, mesh=mesh,
                           compress=pol, lr=args.lr)
    layout = progs.layout
    warm_step, comp_step = progs.warm_step, progs.compressed_step
    wire = progs.wire_state
    param_flat = progs.state["param_flat"]
    accum = progs.state["accum"]
    f32_bytes = layout.padded_total * 4
    print(f"quantized wire ({scheme}): {m} workers x {layout.num_groups} "
          f"groups; route "
          f"{pol.wire_bytes(layout) / 1e6:.2f}MB/worker/step vs "
          f"{f32_bytes / 1e6:.2f}MB f32 "
          f"(ratio {pol.compression_ratio(layout):.3f}); "
          f"warmup {pol.warmup_steps} steps f32, then "
          f"{pol.wire_dtype()} payload + "
          f"{pol.sideband_floats_per_tile()} f32 sideband(s)/tile; "
          f"wire state: {', '.join(pol.state_names())}")
    t0 = time.perf_counter()
    for i in range(args.steps):
        b = stream.batch(i)
        batch = {"tokens": jnp.asarray(b["tokens"]),
                 "labels": jnp.asarray(b["labels"])}
        if cfg.family == "vlm":
            batch["image_embeds"] = jnp.zeros(
                (args.batch, cfg.num_image_tokens, cfg.d_model),
                jnp.dtype(cfg.dtype))
        if cfg.family == "audio":
            batch["frames"] = jnp.zeros(
                (args.batch, cfg.encoder_frames, cfg.d_model),
                jnp.dtype(cfg.dtype))
        tokens = jnp.full((m,), i, jnp.int32)
        gstep = jnp.asarray(i, jnp.int32)
        warm = i < pol.warmup_steps
        fn = warm_step if warm else comp_step
        param_flat, accum, loss, wire = fn(
            param_flat, accum, batch, tokens, gstep, wire)
        if i % 5 == 0 or i == args.steps - 1 or i == pol.warmup_steps:
            phase = "warmup/f32" if warm else f"{scheme} wire"
            print(f"step {i:4d}  loss {float(loss):.4f}  [{phase}]  "
                  f"{(i + 1) * args.batch * args.seq / (time.perf_counter() - t0):,.0f} tok/s")
    assert jnp.isfinite(loss), "quantized-wire run diverged"


def run_autoswitch(args, cfg, mesh, params) -> None:
    """End-to-end tuning-free switching on this arch's REAL compiled
    steps: SwitchDriver runs sync (pytree psum + Adagrad) vs async
    (token-controlled fused-psum on the canonical layer-grouped layout)
    under the ``--plan`` fault plan, switching on live telemetry."""
    from repro.core.autoswitch import AutoSwitchController
    from repro.launch.programs import make_loss_fn
    from repro.launch.switch_driver import (SwitchConfig, SwitchDriver,
                                            demo_plan)
    from repro.sim.cluster import ClusterSpec

    m = mesh.shape["data"]
    gba = GBAConfig(local_batch=args.batch, buffer_size=m,
                    staleness_tolerance=args.iota)
    # build_programs for the canonical layer-grouped layout only — the
    # driver compiles its own sync/async program pair from it
    layout = build_programs(cfg, gba, mode="fused", params=params,
                            mesh=mesh, place_state=False).layout
    stream = make_lm_stream(cfg.vocab_size, args.seq, args.batch, seed=0)

    def batch_fn(i: int) -> dict:
        b = stream.batch(i)
        return {"tokens": b["tokens"], "labels": b["labels"]}

    spec = ClusterSpec(num_workers=m, base_speed=10_000.0, jitter=0.05,
                       allreduce_latency=0.005, ps_roundtrip=0.001,
                       seed=0)
    plan = demo_plan(args.plan, m)
    swcfg = SwitchConfig(local_batch=args.batch, iota=args.iota,
                         lr=args.lr)
    driver = SwitchDriver(mesh, make_loss_fn(cfg), params, spec=spec,
                          plan=plan, cfg=swcfg, batch_fn=batch_fn,
                          layout=layout)
    res = driver.run(args.batches, mode="auto",
                     controller=AutoSwitchController(
                         min_dwell=swcfg.min_dwell))
    print(f"autoswitch ({args.plan}): {res.num_global_steps} global "
          f"steps, {res.switch_count} switch(es), mode steps "
          f"{res.mode_steps}, first switch at gstep "
          f"{res.time_to_first_switch_steps}, sim qps {res.qps:,.0f}, "
          f"crashes {res.crashes} rejoins {res.rejoins} timeouts "
          f"{res.timeouts}, swaps verified {res.swaps_verified}, "
          f"final loss {res.losses[-1] if res.losses else float('nan'):.4f}")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS,
                    help="LM architecture (required unless --vocab)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--buffer", type=int, default=4, help="GBA M")
    ap.add_argument("--iota", type=int, default=4)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke variant on the 1-device mesh (CPU)")
    ap.add_argument("--fused", action="store_true",
                    help="flat-buffer GBA + fused gba_apply kernel; "
                         "FORCES Adagrad (implied for Adagrad archs with "
                         "--reduced); under a multi-device --mesh the "
                         "flat state shards per-slice (one launch per "
                         "PS shard)")
    ap.add_argument("--mesh", default="",
                    help="explicit DATAxMODEL mesh, e.g. 4x1; overrides "
                         "the smoke/production default")
    ap.add_argument("--layer-groups", choices=("auto", "on", "off"),
                    default="auto",
                    help="layer-grouped flat layout for the sharded fused "
                         "state: per-group contiguous shard-aligned "
                         "slices, so the grouped collective schedule "
                         "gathers one layer group at a time (peak gather "
                         "= largest group, not N_total).  auto = on for "
                         "--fused with a multi-device --mesh")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force N host-platform devices before jax device "
                         "init (CPU test path for --mesh)")
    ap.add_argument("--compress", choices=("none", "int8", "onebit"),
                    default="none",
                    help="quantize the gradient routing wire of the "
                         "worker-parallel fused-psum step (implies that "
                         "step; needs --fused and a multi-device data "
                         "axis).  int8 = per-tile min-max with error "
                         "feedback; onebit = sign-of-momentum after "
                         "--compress-warmup full-precision global steps. "
                         "Sync / single-device runs auto-fall back to "
                         "none — there is no wire to compress")
    ap.add_argument("--compress-warmup", type=int, default=2,
                    help="full-precision warmup global steps before the "
                         "lossy wire engages (re-jit at the boundary)")
    ap.add_argument("--autoswitch", action="store_true",
                    help="run the end-to-end switching harness "
                         "(launch.switch_driver) on this arch's compiled "
                         "sync/async steps under a --plan fault plan "
                         "(needs a multi-device data axis)")
    ap.add_argument("--plan", choices=("quiet", "strained"),
                    default="strained",
                    help="fault plan for --autoswitch: quiet (vacant "
                         "cluster) or strained (25%% stragglers at 4x + "
                         "one transient crash)")
    ap.add_argument("--batches", type=int, default=120,
                    help="local batches to stream through --autoswitch")
    ap.add_argument("--vocab", type=int, default=0,
                    help="run the streamed-embedding sparse smoke at this "
                         "hash capacity (e.g. 1000000) instead of an LM "
                         "arch")
    ap.add_argument("--embed-dim", type=int, default=16)
    ap.add_argument("--block-v", type=int, default=0,
                    help="vocab rows per streamed table tile (0 = default)")
    ap.add_argument("--block-d", type=int, default=0,
                    help="embedding cols per output tile (0 = default)")
    ap.add_argument("--chunk-e", type=int, default=0,
                    help="sorted entries per pipeline step (0 = default)")
    return ap


def main() -> None:
    ap = build_parser()
    args = ap.parse_args()
    enable_compile_cache()

    if args.vocab:
        run_embedding_smoke(args)
        return
    if not args.arch:
        ap.error("--arch is required unless --vocab is given")

    cfg = get_config(args.arch)
    # resolve the optimizer from the canonical name BEFORE .reduced()
    # renames the config (…-smoke), so smoke runs match production
    opt_name = ARCH_OPTIMIZER.get(cfg.name, "adam")
    if args.reduced:
        cfg = cfg.reduced()
    if args.mesh:
        d, _, t = args.mesh.partition("x")
        shape = (int(d), int(t or 1))
        if jax.device_count() < shape[0] * shape[1]:
            ap.error(f"--mesh {args.mesh} needs {shape[0] * shape[1]} "
                     f"devices, have {jax.device_count()} "
                     f"(use --host-devices on CPU)")
        mesh = make_mesh(shape, ("data", "model"))
    elif args.reduced:
        mesh = make_smoke_mesh()
    else:
        mesh = make_production_mesh()

    params = T.init_model(jax.random.PRNGKey(0), cfg)
    print(f"{cfg.name}: {T.param_count(params) / 1e6:.1f}M params, "
          f"mesh {dict(mesh.shape)}")
    if args.autoswitch:
        if mesh.shape["data"] < 2:
            ap.error("--autoswitch needs a multi-device data axis "
                     "(e.g. --mesh 4x1 --host-devices 4 on CPU)")
        with mesh:
            run_autoswitch(args, cfg, mesh, params)
        return
    # the fused flat buffer is single-host (no per-leaf shardings) and
    # costs buffer_size f32 copies of the params: auto-enable only for
    # Adagrad archs on the smoke mesh, explicit --fused elsewhere
    fused = args.fused or (opt_name == "adagrad" and args.reduced)
    if fused and opt_name != "adagrad":
        print(f"--fused forces Adagrad (arch default was {opt_name})")
    opt = get_optimizer(opt_name, args.lr)
    gba = GBAConfig(local_batch=args.batch, buffer_size=args.buffer,
                    staleness_tolerance=args.iota)
    stream = make_lm_stream(cfg.vocab_size, args.seq, args.batch, seed=0)

    # keyed off the actual mesh, not --mesh: the sharded fused path (and
    # so the grouped layout) engages whenever the data axis is >1 wide,
    # including the production default mesh
    multi_dev = mesh.shape["data"] > 1
    layer_groups = (args.layer_groups == "on"
                    or (args.layer_groups == "auto" and fused and multi_dev))
    compress = args.compress
    if compress != "none" and not (fused and multi_dev):
        # sync / single-device mode has no worker->shard wire to quantize
        print(f"--compress {compress}: needs --fused and a multi-device "
              f"data axis (worker-parallel fused-psum wire); this "
              f"sync/single-device run falls back to none")
        compress = "none"
    if compress != "none":
        if args.batch % mesh.shape["data"]:
            ap.error(f"--compress needs --batch divisible by the data "
                     f"axis ({mesh.shape['data']})")
        with mesh:
            run_wire_train(args, cfg, mesh, gba, stream, params, compress)
        return
    with mesh:
        if fused:
            progs = build_programs(cfg, gba, mode="fused", params=params,
                                   mesh=mesh, lr=args.lr,
                                   layer_groups=layer_groups)
            layout, state, step_fn = progs.layout, progs.state, progs.step
            from repro.core.flat_sharded import ShardedFlatLayout
            if isinstance(layout, ShardedFlatLayout):
                print(f"sharded fused gba_apply path (Adagrad): flat "
                      f"buffer ({gba.buffer_size}, {layout.padded_total}) "
                      f"sliced over data={layout.num_shards} "
                      f"(shard_size={layout.shard_size}, "
                      f"tile={layout.tile}; 1 apply launch/shard vs "
                      f"{len(layout.sizes)} per-leaf)")
                if layout.num_groups > 1:
                    table = ", ".join(
                        f"{r['key']}={r['bytes'] / 1e6:.2f}MB"
                        for r in layout.group_table())
                    print(f"layer groups ({layout.num_groups}): {table}; "
                          f"peak_gather="
                          f"{layout.peak_gather_bytes / 1e6:.2f}MB vs "
                          f"full_gather="
                          f"{layout.full_gather_bytes / 1e6:.2f}MB")
            else:
                print(f"fused gba_apply path (Adagrad): flat buffer "
                      f"({gba.buffer_size}, {layout.total})")
        else:
            progs = build_programs(cfg, gba, mode="pytree", params=params,
                                   optimizer=opt, acc_dtype=jnp.float32)
            step_fn, state = progs.step, progs.state
        t0 = time.perf_counter()
        for i in range(args.steps):
            b = stream.batch(i)
            batch = {"tokens": jnp.asarray(b["tokens"]),
                     "labels": jnp.asarray(b["labels"])}
            if cfg.family == "vlm":
                batch["image_embeds"] = jnp.zeros(
                    (args.batch, cfg.num_image_tokens, cfg.d_model),
                    jnp.dtype(cfg.dtype))
            if cfg.family == "audio":
                batch["frames"] = jnp.zeros(
                    (args.batch, cfg.encoder_frames, cfg.d_model),
                    jnp.dtype(cfg.dtype))
            token = jnp.asarray(i // args.buffer, jnp.int32)
            state, loss = step_fn(state, batch, token)
            if i % 5 == 0 or i == args.steps - 1:
                gstep = int(state["buffer"]["step"] if fused
                            else state["gstep"])
                print(f"step {i:4d}  loss {float(loss):.4f}  "
                      f"gstep {gstep}  "
                      f"{(i + 1) * args.batch * args.seq /  (time.perf_counter() - t0):,.0f} tok/s")


if __name__ == "__main__":
    main()
