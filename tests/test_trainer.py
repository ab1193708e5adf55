"""Replay-trainer integration: PS semantics, mode parity, per-ID rescue."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.recsys import CRITEO_DEEPFM
from repro.core import GBATrainer, default_setups, run_continual
from repro.core.trainer import evaluate
from repro.data import make_clickstream
from repro.models.recsys import init_recsys
from repro.optim import get_optimizer
from repro.sim.cluster import ClusterSpec, Schedule, Slot, simulate

CFG = CRITEO_DEEPFM


def _stream(bs=128):
    return make_clickstream(CFG, seed=0, batches_per_day=16, batch_size=bs)


def test_sync_replay_reduces_loss():
    stream = _stream()
    params = init_recsys(jax.random.PRNGKey(0), CFG)
    opt = get_optimizer("adam", 1e-3)
    trainer = GBATrainer(CFG, opt)
    spec = ClusterSpec(num_workers=8, seed=0)
    sched = simulate(spec, "sync", 64, 128)
    params, _, _, stats = trainer.replay(params, opt.init(params), sched,
                                         stream, day=0)
    assert stats.losses[-1] < stats.losses[0]
    assert stats.applied_steps == 8
    assert stats.dropped_slots == 0


def test_gba_zero_staleness_equals_sync():
    """A GBA schedule with all-fresh tokens must produce exactly the sync
    update sequence (same batches, same aggregation)."""
    stream = _stream()
    opt = get_optimizer("sgd", 0.1)

    def run(mode_schedule):
        params = init_recsys(jax.random.PRNGKey(1), CFG)
        trainer = GBATrainer(CFG, opt)
        p, _, _, _ = trainer.replay(params, opt.init(params), mode_schedule,
                                    stream, day=0)
        return p

    steps = [[Slot(k * 4 + i, k, k, 1.0) for i in range(4)]
             for k in range(4)]
    sync_like = Schedule("sync", 128, steps)
    gba_like = Schedule("gba", 128, steps)
    p1, p2 = run(sync_like), run(gba_like)
    for k in ("bias",):
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p2[k]),
                                   rtol=1e-5)
    np.testing.assert_allclose(np.asarray(p1["embed"]),
                               np.asarray(p2["embed"]), rtol=1e-4, atol=1e-7)


def test_stale_slots_change_update_and_are_counted():
    stream = _stream()
    opt = get_optimizer("sgd", 0.1)
    params = init_recsys(jax.random.PRNGKey(1), CFG)
    trainer = GBATrainer(CFG, opt, iota=1)
    # second step has one severely stale slot (token 0 applied at step 5)
    steps = [[Slot(i, 0, 0, 1.0) for i in range(4)],
             [Slot(4, 5, 0, 1.0), Slot(5, 5, 0, 1.0),
              Slot(6, 0, 0, 0.0), Slot(7, 5, 0, 1.0)]]
    sched = Schedule("gba", 128, steps)
    _, _, _, stats = trainer.replay(params, opt.init(params), sched,
                                    stream, day=0)
    assert stats.dropped_slots == 1
    assert stats.kept_slots == 7


def test_continual_switch_sync_to_gba_holds_auc():
    """The headline claim (C2): switching sync->GBA does not collapse AUC."""
    stream = _stream(256)
    setups = default_setups(base_global=2048)
    spec = ClusterSpec(num_workers=16, straggler_frac=0.25, seed=0)
    params = init_recsys(jax.random.PRNGKey(0), CFG)
    params, res = run_continual(params, CFG, stream,
                                ["sync"] * 4, setups, spec, eval_batches=6)
    base_auc = res.auc_per_day[-1]
    _, res2 = run_continual(params, CFG, stream, ["gba"], setups, spec,
                            eval_batches=6, start_day=4)
    assert res2.auc_per_day[0] > base_auc - 0.02, \
        f"GBA switch dropped AUC: {base_auc:.4f} -> {res2.auc_per_day[0]:.4f}"


def test_history_ring_clamps_counted():
    stream = _stream()
    opt = get_optimizer("sgd", 0.1)
    params = init_recsys(jax.random.PRNGKey(1), CFG)
    trainer = GBATrainer(CFG, opt, history=2)
    steps = [[Slot(0, 0, 0, 1.0)], [Slot(1, 1, 1, 1.0)],
             [Slot(2, 2, 2, 1.0)], [Slot(3, 3, 0, 1.0)]]  # dispatch 0 @ k=3
    sched = Schedule("gba", 128, steps)
    _, _, _, stats = trainer.replay(params, opt.init(params), sched,
                                    stream, day=0)
    assert stats.history_clamps >= 1


def test_streamed_presence_counts_match_default_path():
    """GBATrainer(embed_stream=...) routes the per-slot presence counts
    through the DMA-streamed sorted-scatter kernel; the replayed parameters
    must match the XLA one-hot-scatter path exactly (same counts, same
    masks, same updates)."""
    from repro.embeddings import StreamConfig

    cfg = dataclasses.replace(CRITEO_DEEPFM, name="criteo-deepfm-tiny",
                              hash_capacity=2048, mlp_dims=(32, 16))
    stream = make_clickstream(cfg, seed=0, batches_per_day=16, batch_size=32)
    opt = get_optimizer("sgd", 0.05)
    # a schedule with real staleness so the per-ID relaxation path runs
    steps = [[Slot(k * 3 + i, max(0, k - i), k, 1.0 if i < 2 else 0.0)
              for i in range(3)] for k in range(4)]
    sched = Schedule("gba", 32, steps)

    def run(embed_stream):
        params = init_recsys(jax.random.PRNGKey(2), cfg)
        trainer = GBATrainer(cfg, opt, iota=1, embed_stream=embed_stream)
        p, _, last_update, stats = trainer.replay(
            params, opt.init(params), sched, stream, day=0)
        return p, last_update, stats

    p1, lu1, st1 = run(None)
    p2, lu2, st2 = run(StreamConfig())
    assert st1.embed_rows_rescued == st2.embed_rows_rescued
    np.testing.assert_array_equal(np.asarray(lu1), np.asarray(lu2))
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(p1),
            jax.tree_util.tree_leaves_with_path(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7,
                                   err_msg=str(path))


TINY = dataclasses.replace(CRITEO_DEEPFM, name="criteo-deepfm-tiny",
                           hash_capacity=2048, mlp_dims=(32, 16))
# 16 slots a step over 2-5 distinct versions each, as a strained GBA step
# holds them: slot i of step k was dispatched LAG[k][i] steps back
LAG = [[0] * 16,
       [i % 2 for i in range(16)],
       [(0, 1, 2, 0)[i % 4] for i in range(16)],
       [min(i // 3, 4) for i in range(16)],
       [(3, 0, 0, 1, 0, 2)[i % 6] for i in range(16)]]
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


def _tiny_stream():
    return make_clickstream(TINY, seed=0, batches_per_day=128, batch_size=32)


def _gba_lagged_schedule() -> Schedule:
    return Schedule("gba", 32, [
        [Slot(16 * k + i, max(0, k - lag), max(0, k - lag), 1.0)
         for i, lag in enumerate(lags)]
        for k, lags in enumerate(LAG)])


def test_version_stack_matches_eager_stack_bitwise():
    """The compiled stacker copies: the (M, ...) tree it returns equals the
    eager per-leaf ``jnp.stack`` bit for bit, for 16 slots over 2-5
    distinct versions (each version's buffer passed once per slot), and a
    GBA replay through the trainer takes it."""
    from repro.core.trainer import _stack_versions

    versions = [init_recsys(jax.random.PRNGKey(s), TINY) for s in range(5)]
    for distinct in (2, 3, 5):
        srcs = [versions[(i * 7) % distinct] for i in range(16)]
        got = _stack_versions(tuple(srcs))
        want = jax.tree.map(lambda *xs: jnp.stack(xs), *srcs)
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                                jax.tree.leaves(want)):
            assert g.shape == w.shape and g.dtype == w.dtype, path
            assert np.array_equal(np.asarray(g), np.asarray(w)), path

    opt = get_optimizer("sgd", 0.05)
    params = init_recsys(jax.random.PRNGKey(3), TINY)
    _, _, _, stats = GBATrainer(TINY, opt, iota=2).replay(
        params, opt.init(params), _gba_lagged_schedule(), _tiny_stream(),
        day=0)
    assert stats.stacked_steps == len(LAG) - 1 > 0


def test_second_gba_replay_traces_and_compiles_nothing():
    """Replaying the same GBA schedule again on the same trainer builds no
    program: the stacker and both step variants are cached from the first
    replay, so nothing retraces from step to step."""
    stream = _tiny_stream()
    opt = get_optimizer("sgd", 0.05)
    params = init_recsys(jax.random.PRNGKey(4), TINY)
    trainer = GBATrainer(TINY, opt, iota=2)
    sched = _gba_lagged_schedule()
    params, state, lu, stats = trainer.replay(params, opt.init(params),
                                              sched, stream, day=0)
    events = []

    def on_event(event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            events.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        _, _, _, stats = trainer.replay(params, state, sched, stream, day=0,
                                        last_update=lu, stats=stats)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert stats.applied_steps == 2 * len(LAG)
    assert stats.stacked_steps == 2 * (len(LAG) - 1)
    assert events == []
