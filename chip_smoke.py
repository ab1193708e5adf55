#!/usr/bin/env python3
"""Chip smoke test: GBA training through its normal entry points on a TPU.

    python3 chip_smoke.py              # phases A, B and C on one chip
    python3 chip_smoke.py --chips 4    # only the 4-chip switching parity

Phases (one process, random weights from fixed seeds, data generated
from seeds; nothing is read from outside the repository):

A. Recsys GBA, the paper's path: ``criteo-deepfm`` at its configured
   widths trained by ``core.GBATrainer`` with the streamed row scatter,
   which also counts each id's contributing slots, replaying a
   strained-cluster GBA schedule of 16 slots x local batch 128.
   Reference: the first global step with the XLA scatter path.
B. LM fused GBA apply: ``mamba2-780m`` at its published widths, cut to 8
   layers, through ``build_programs(mode="fused")`` with M=4.  Reference:
   one apply of the ``gba_apply`` kernel against ``ref.gba_apply_ref``.
C. Streamed embedding: the ``launch.train --vocab 1000000`` path, forward
   and backward, 3 steps.  Reference: ``ref.embedding_bag_ref`` and
   ``ref.embedding_bag_grad_ref`` on one batch.

``--chips 4`` runs ``SwitchDriver.run_schedule`` forced sync->gba->sync on
a (4,) data mesh with the phase-B model, against the unswitched sync and
gba replays of the same schedule; then the same switched replay with the
pytree-psum sync, against the fused one, and a planted psum sync step
that applies nothing, which that comparison must reject.

Each phase prints its compile time (JAX's trace, lowering and backend
compile events, persistent-cache hits included), its steps, its reference
comparison against a stated tolerance, and the device's
``peak_bytes_in_use`` (the process peak so far).  The script exits
non-zero without a TPU, with Pallas kernels in interpret mode, outside a
checkout of the repository, or when any check fails.  Its last stdout
line is the JSON result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# phase A: both paths give identical integer contributor counts, so the
# first step may differ only by the order of the float32 row sums
RECSYS_PARAM_TOL = 1e-6
# phase B: the kernel sums the M rows in worker order and runs Adagrad's
# sqrt/divide in Mosaic; the oracle is XLA's reduction and elementwise ops
APPLY_PARAM_TOL = 1e-6
APPLY_ACCUM_RTOL = 1e-6
# phase C: one-hot matmuls at HIGHEST precision move table values exactly;
# only the summation order of the pooled / scattered rows differs
EMBED_TOL = 1e-5
# --chips 4: one program family (sync_impl="fused") is bit-exact whether or
# not it swaps.  The pytree-psum sync keeps bf16 params between swaps and
# all-reduces bf16 gradients, so its Adagrad accumulator update differs
# from the f32 fused path's by bf16 rounding of g: ||a - a_fused|| /
# ||a_fused - a0|| read 1.3e-2 to 1.7e-2 on 2-layer CPU runs of this
# schedule, and 0.77 for a sync step that applies nothing
SWITCH_FUSED_TOL = 0.0
SWITCH_PSUM_ACCUM_RTOL = 0.1

LR = 1e-3
IOTA = 4


class CompileClock:
    """Seconds spent tracing, lowering and compiling, summed from JAX's
    ``/jax/core/compile/*`` duration events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.total += duration


def check(ok: bool, what: str) -> None:
    print(f"  check {'ok' if ok else 'FAILED'}: {what}", flush=True)
    if not ok:
        raise RuntimeError(f"chip smoke check failed: {what}")


def peak_bytes() -> int:
    import jax
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())


def run_phase(name: str, fn, clock: CompileClock) -> None:
    print(f"== phase {name} ==", flush=True)
    c0 = clock.total
    fn()
    print(f"[{name}] compile_s={clock.total - c0:.2f} "
          f"peak_bytes_in_use={peak_bytes()}", flush=True)


def max_abs_diff(a, b) -> float:
    import jax
    import jax.numpy as jnp
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def accum_update_rel_err(accum, accum_ref, accum0: float) -> float:
    """How far a run's accumulator update is from the reference run's,
    relative to the reference update: ``||a - a_ref|| / ||a_ref - a0||``."""
    import numpy as np
    ref = accum_ref.astype(np.float64)
    return float(np.linalg.norm(accum.astype(np.float64) - ref)
                 / np.linalg.norm(ref - accum0))


# ---------------------------------------------------------------------------
# A. recsys GBA replay
# ---------------------------------------------------------------------------

def phase_recsys(cfg=None, *, num_batches: int = 256, workers: int = 16,
                 local_batch: int = 128, eval_batches: int = 8) -> None:
    import jax
    import numpy as np
    from repro.configs.recsys import CRITEO_DEEPFM
    from repro.core import GBATrainer, evaluate, schedule_for_day
    from repro.core.continual import ModeSetup
    from repro.data import make_clickstream
    from repro.embeddings.table import StreamConfig
    from repro.kernels import ops
    from repro.models.recsys import init_recsys
    from repro.optim import get_optimizer
    from repro.sim.cluster import ClusterSpec, Schedule

    cfg = cfg or CRITEO_DEEPFM
    stream = make_clickstream(cfg, seed=0, batch_size=local_batch)
    params0 = init_recsys(jax.random.PRNGKey(0), cfg)
    optimizer = get_optimizer("adam", LR)
    setup = ModeSetup("gba", num_workers=workers, local_batch=local_batch,
                      buffer_size=workers, iota=IOTA)
    spec = ClusterSpec(num_workers=workers, straggler_frac=0.25,
                       straggler_slowdown=5.0, jitter=0.2, seed=0)
    sched = schedule_for_day(setup, spec, num_batches=num_batches)
    print(f"  {cfg.name}: {cfg.num_fields} fields, D={cfg.embed_dim}, MLP "
          f"{tuple(cfg.mlp_dims)}, {cfg.hash_capacity:,} rows; schedule "
          f"{len(sched.steps)} global steps of {workers} slots x "
          f"{local_batch}")

    streamed = GBATrainer(cfg, optimizer, iota=IOTA,
                          embed_stream=StreamConfig())
    first = Schedule(sched.mode, sched.local_batch, sched.steps[:1])
    calls0 = ops.kernel_calls["pooled_lookup_grad"]
    p_kernel, *_ = streamed.replay(params0, optimizer.init(params0), first,
                                   stream, 0)
    check(ops.kernel_calls["pooled_lookup_grad"] == calls0,
          "no pooled-backward kernel traced: the contributor counts ride "
          "in the row scatter")
    p_xla, *_ = GBATrainer(cfg, optimizer, iota=IOTA).replay(
        params0, optimizer.init(params0), first, stream, 0)
    diff = max_abs_diff(p_kernel, p_xla)
    check(diff <= RECSYS_PARAM_TOL,
          f"first global step, kernel vs XLA scatter path: max|dparam|="
          f"{diff:.3e} <= {RECSYS_PARAM_TOL:g}")

    params, _, _, stats = streamed.replay(
        params0, optimizer.init(params0), sched, stream, 0)
    losses = np.asarray(stats.losses)
    for k in range(0, len(losses), 4):
        print(f"  step {k:3d}  loss {losses[k]:.4f}")
    print(f"  {stats.stacked_steps} of {stats.applied_steps} steps stacked "
          f"parameter versions; step variants (gba, m, shared_src) built "
          f"by step {stats.step_builds}")
    check(stats.applied_steps >= 8,
          f"{stats.applied_steps} global steps applied (>= 8); kept "
          f"{stats.kept_slots} slots, dropped {stats.dropped_slots}")
    check(bool(np.all(np.isfinite(losses))),
          f"losses finite (last {losses[-1]:.4f})")
    auc = evaluate(params, cfg, stream, 1, num_batches=eval_batches)
    print(f"  next-day AUC {auc:.4f} ({eval_batches} batches)")
    check(bool(np.isfinite(auc)), "next-day AUC finite")


# ---------------------------------------------------------------------------
# B. LM fused GBA apply
# ---------------------------------------------------------------------------

def lm_config(num_layers: int = 8):
    """``mamba2-780m`` at its published widths, cut in depth only."""
    from repro.configs import get_config
    return dataclasses.replace(get_config("mamba2-780m"),
                               num_layers=num_layers)


def phase_lm_fused(cfg=None, *, batch: int = 4, seq: int = 512,
                   buffer: int = 4, microsteps: int = 8) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs.base import GBAConfig
    from repro.data import make_lm_stream
    from repro.kernels import ops, ref
    from repro.launch.programs import build_programs
    from repro.models import transformer as T

    cfg = cfg or lm_config()
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    n = T.param_count(params)
    gba = GBAConfig(local_batch=batch, buffer_size=buffer,
                    staleness_tolerance=IOTA)
    progs = build_programs(cfg, gba, mode="fused", params=params, lr=LR)
    del params
    layout, state, step = progs.layout, progs.state, progs.step
    print(f"  {cfg.name} x{cfg.num_layers} layers: d_model {cfg.d_model}, "
          f"vocab {cfg.vocab_size:,}, {n:,} params ({cfg.dtype})")
    print(f"  bytes: f32 buffer {buffer} x {n:,} x 4 = {buffer * n * 4:,}; "
          f"f32 flat params at apply {n * 4:,}; f32 accum {n * 4:,}; "
          f"total {(buffer + 2) * n * 4:,} ({(buffer + 2) * 4} B/param)")
    stream = make_lm_stream(cfg.vocab_size, seq, batch, seed=0)
    losses = []
    for i in range(microsteps):
        b = stream.batch(i)
        state, loss = step(state, {"tokens": jnp.asarray(b["tokens"]),
                                   "labels": jnp.asarray(b["labels"])},
                           jnp.asarray(i // buffer, jnp.int32))
        losses.append(float(loss))
        print(f"  microstep {i}  loss {losses[-1]:.4f}  "
              f"gstep {int(state['buffer']['step'])}")
    check(all(map(math.isfinite, losses)), "losses finite")
    gstep = int(state["buffer"]["step"])
    check(gstep >= 2, f"gstep advanced to {gstep} (>= 2)")

    # one apply on the trained state, with slot 0 made stale so Eq. (1)
    # drops it: the kernel vs the two-pass oracle, both on this chip
    tokens = state["buffer"]["tokens"].at[0].set(gstep - IOTA - 2)
    step_no = jnp.asarray(gstep, jnp.int32)

    @jax.jit
    def apply_diffs(p, a, buf, tok, s):
        kp, ka = ops.gba_apply_flat(p, a, buf, tok, s, LR, iota=IOTA)
        rp, ra = ref.gba_apply_ref(p, a, buf, tok, s, LR, iota=IOTA)
        return (jnp.max(jnp.abs(kp - rp)),
                jnp.max(jnp.abs(ka - ra) / jnp.abs(ra)),
                jnp.max(jnp.abs(kp - p)))

    dp, da, moved = map(float, apply_diffs(
        layout.ravel(state["params"]), state["accum"],
        state["buffer"]["grads"], tokens, step_no))
    check(moved > 0.0, f"apply moved the params (max|dp|={moved:.3e})")
    check(dp <= APPLY_PARAM_TOL,
          f"gba_apply vs gba_apply_ref: max|dparam|={dp:.3e} <= "
          f"{APPLY_PARAM_TOL:g}")
    check(da <= APPLY_ACCUM_RTOL,
          f"gba_apply vs gba_apply_ref: max rel|daccum|={da:.3e} <= "
          f"{APPLY_ACCUM_RTOL:g}")


# ---------------------------------------------------------------------------
# C. streamed embedding
# ---------------------------------------------------------------------------

def phase_embedding(*, vocab: int = 1_000_000, batch: int = 512,
                    steps: int = 3) -> None:
    import jax
    import jax.numpy as jnp
    from repro import embeddings
    from repro.kernels import ops, ref
    from repro.launch import train

    args = train.build_parser().parse_args(
        ["--vocab", str(vocab), "--steps", str(steps),
         "--batch", str(batch)])
    table = train.run_embedding_smoke(args)

    key = jax.random.PRNGKey(7)
    ids = embeddings.hash_ids(
        jax.random.randint(key, (batch, 26), 0, 1 << 30), vocab)
    g = jax.random.normal(key, (batch, args.embed_dim), jnp.float32)

    @jax.jit
    def diffs(table, ids, g):
        out = embeddings.pooled_lookup(
            embeddings.EmbeddingTable(table, jnp.zeros((vocab,), jnp.int32)),
            ids)
        gt, cnt = ops.pooled_lookup_grad(ids, g, vocab)
        gt_ref, cnt_ref = ref.embedding_bag_grad_ref(ids, g, vocab)
        return (jnp.max(jnp.abs(out - ref.embedding_bag_ref(ids, table))),
                jnp.max(jnp.abs(gt - gt_ref)),
                jnp.max(jnp.abs(cnt - cnt_ref)))

    fwd, bwd, cnt = map(float, diffs(table, ids, g))
    check(fwd <= EMBED_TOL, f"pooled_lookup vs embedding_bag_ref: "
          f"max|d|={fwd:.3e} <= {EMBED_TOL:g}")
    check(bwd <= EMBED_TOL, f"pooled_lookup_grad vs embedding_bag_grad_ref:"
          f" max|d|={bwd:.3e} <= {EMBED_TOL:g}")
    check(cnt == 0.0, f"contributor counts exact (max|d|={cnt:g})")


# ---------------------------------------------------------------------------
# --chips 4: forced switching parity
# ---------------------------------------------------------------------------

def switch_schedule(m: int = 4):
    """8 global steps; step 5 carries an Eq.-(1)-decayed slot and a
    tombstone slot (batch -1)."""
    from repro.launch.switch_driver import GlobalStep
    steps, b = [], 0
    for k in range(8):
        toks, bats = [k] * m, list(range(b, b + m))
        b += m
        if k == 5:
            toks[1] = 0
            toks[2], bats[2] = k - IOTA - 1, -1
        steps.append(GlobalStep(tuple(toks), tuple(bats)))
    return steps, ["sync"] * 3 + ["gba"] * 3 + ["sync"] * 2


def phase_switch(cfg=None, *, local_batch: int = 4, seq: int = 256) -> None:
    import jax
    import numpy as np
    from repro.data import make_lm_stream
    from repro.launch.mesh import make_mesh
    from repro.launch.programs import make_loss_fn
    from repro.launch.switch_driver import SwitchConfig, SwitchDriver
    from repro.models import transformer as T
    from repro.sim.cluster import ClusterSpec
    from repro.sim.faults import FaultPlan

    cfg = cfg or lm_config()
    mesh = make_mesh((4,), ("data",))
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    stream = make_lm_stream(cfg.vocab_size, seq, local_batch, seed=0)

    def batch_fn(i: int) -> dict:
        b = stream.batch(i)
        return {"tokens": b["tokens"], "labels": b["labels"]}

    steps, modes = switch_schedule(4)
    print(f"  {cfg.name} x{cfg.num_layers} layers, "
          f"{T.param_count(params):,} params on mesh {dict(mesh.shape)}; "
          f"modes {''.join(m[0] for m in modes)}")

    def driver(sync_impl: str) -> SwitchDriver:
        return SwitchDriver(
            mesh, make_loss_fn(cfg), params, spec=ClusterSpec(num_workers=4),
            plan=FaultPlan.quiet(4),
            cfg=SwitchConfig(local_batch=local_batch, iota=IOTA, lr=LR,
                             sync_impl=sync_impl, verify_swap=True),
            batch_fn=batch_fn, group_by=T.param_group_key)

    fused = driver("fused")
    r_sw = fused.run_schedule(steps, modes)
    r_gba = fused.run_schedule(steps, ["gba"] * len(steps))
    r_sync = fused.run_schedule(steps, ["sync"] * len(steps))
    print(f"  switched losses {np.round(r_sw.losses, 4).tolist()}")
    check(r_sw.switch_count == 2 and r_sw.dropped_batches == 1
          and r_sw.tombstones == 1,
          f"{r_sw.switch_count} switches, {r_sw.dropped_batches} decayed "
          f"slot, {r_sw.tombstones} tombstone")
    check(bool(np.all(np.isfinite(r_sw.losses))), "losses finite")
    for name, r in (("gba", r_gba), ("sync", r_sync)):
        dp = float(np.max(np.abs(r_sw.param_flat - r.param_flat)))
        da = float(np.max(np.abs(r_sw.accum_flat - r.accum_flat)))
        check(dp <= SWITCH_FUSED_TOL and da <= SWITCH_FUSED_TOL,
              f"switched vs unswitched {name} (fused sync): "
              f"max|dparam|={dp:.3e} max|daccum|={da:.3e} <= "
              f"{SWITCH_FUSED_TOL:g}")
    check(r_sw.losses == r_gba.losses, "per-step losses equal (fused)")

    # psum sync against the fused oracle, on the f32 Adagrad accumulator:
    # bf16 params cannot hold most single-step updates (they are below
    # half an ulp), so only the accumulator shows what the sync steps did
    psum = driver("psum")
    r_psum = psum.run_schedule(steps, modes)
    check(r_psum.swaps_verified == 2,
          f"{r_psum.swaps_verified} swaps verified bit-exact (psum sync)")
    # negative control: a psum sync step that leaves the state unchanged
    psum._sync_step = lambda p, o, *_: (p, o, np.float32(0.0))
    r_noop = psum.run_schedule(steps, modes)
    a0 = fused.cfg.initial_accum
    rel = {name: accum_update_rel_err(r.accum_flat, r_sw.accum_flat, a0)
           for name, r in (("psum", r_psum), ("no-op", r_noop))}
    print(f"  params, bf16-rounded psum vs f32-master fused (not checked): "
          f"max|dparam|={np.max(np.abs(r_psum.param_flat - r_sw.param_flat)):.3e}")
    check(rel["psum"] <= SWITCH_PSUM_ACCUM_RTOL,
          f"psum-sync switched vs fused switched: accum update rel err "
          f"{rel['psum']:.3e} <= {SWITCH_PSUM_ACCUM_RTOL:g}")
    check(rel["no-op"] > SWITCH_PSUM_ACCUM_RTOL,
          f"planted no-op psum sync fails the same check: accum update "
          f"rel err {rel['no-op']:.3e} > {SWITCH_PSUM_ACCUM_RTOL:g}")


# ---------------------------------------------------------------------------

def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run only the 4-chip switching parity")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: {ROOT} is not a checkout of this repository "
              f"(src/repro is missing)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    from repro.kernels import runtime
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found — JAX reports platform "
              f"{dev.platform!r} ({len(devices)} device(s)); this smoke "
              f"test measures nothing off the chip", file=sys.stderr)
        return 1
    if runtime.interpret_mode() is not False:
        print("chip_smoke: Pallas kernels would run in interpret mode on "
              "the TPU (REPRO_INTERPRET or set_interpret forces it); "
              "refusing", file=sys.stderr)
        return 1
    print(f"platform {dev.platform}  device_kind {dev.device_kind}  "
          f"device_count {len(devices)}  compile_cache {cache_dir}",
          flush=True)

    clock = CompileClock()
    if args.chips == 4:
        if len(devices) < 4:
            print(f"chip_smoke: --chips 4 needs 4 devices, JAX reports "
                  f"{len(devices)}", file=sys.stderr)
            return 1
        run_phase("4chip-switch", phase_switch, clock)
    else:
        run_phase("A-recsys-gba", phase_recsys, clock)
        run_phase("B-lm-fused-apply", phase_lm_fused, clock)
        run_phase("C-streamed-embedding", phase_embedding, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
