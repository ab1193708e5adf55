"""Replay-trainer integration: PS semantics, mode parity, per-ID rescue."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.recsys import CRITEO_DEEPFM, RecsysConfig
from repro.core import GBATrainer, default_setups, run_continual
from repro.core.trainer import evaluate
from repro.data import make_clickstream
from repro.models import recsys as R
from repro.models.recsys import init_recsys
from repro.optim import Optimizer, get_optimizer
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


# one step in two has a zero-weight slot, whose ids the count leaves out
SYNC_ZERO_WEIGHT = [[Slot(k * 3 + i, k, k, 0.0 if (i, k % 2) == (1, 1)
                          else 1.0) for i in range(3)] for k in range(4)]


@pytest.mark.parametrize("mode", ["gba", "sync"])
def test_streamed_presence_counts_match_default_path(mode):
    """GBATrainer(embed_stream=...) counts each id's contributing slots in
    the streamed row scatter's own sort; the XLA path counts them with a
    per-slot scatter.  ``last_update`` and the rescued rows must match
    exactly, the replayed parameters up to the order of the float32 row
    sums."""
    from repro.embeddings import StreamConfig

    cfg = dataclasses.replace(CRITEO_DEEPFM, name="criteo-deepfm-tiny",
                              hash_capacity=2048, mlp_dims=(32, 16))
    stream = make_clickstream(cfg, seed=0, batches_per_day=16, batch_size=32)
    opt = get_optimizer("sgd", 0.05)
    if mode == "gba":
        # real staleness, so the per-ID relaxation path runs
        steps = [[Slot(k * 3 + i, max(0, k - i), k, 1.0 if i < 2 else 0.0)
                  for i in range(3)] for k in range(4)]
    else:
        steps = SYNC_ZERO_WEIGHT
    sched = Schedule(mode, 32, steps)

    def run(embed_stream):
        params = init_recsys(jax.random.PRNGKey(2), cfg)
        trainer = GBATrainer(cfg, opt, iota=1, embed_stream=embed_stream)
        p, _, last_update, stats = trainer.replay(
            params, opt.init(params), sched, stream, day=0)
        return p, last_update, stats

    p1, lu1, st1 = run(None)
    p2, lu2, st2 = run(StreamConfig())
    assert st1.embed_rows_rescued == st2.embed_rows_rescued
    assert (st1.embed_rows_rescued > 0) == (mode == "gba")
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


# -- the sparse gradient: one row scatter against per-slot table gradients --

ROW_CFGS = {
    "deepfm": RecsysConfig(name="deepfm-rows", model="deepfm", num_fields=5,
                           hash_capacity=512, embed_dim=6, mlp_dims=(8,)),
    "youtubednn": RecsysConfig(name="youtubednn-rows", model="youtubednn",
                               num_fields=3, hash_capacity=512, embed_dim=6,
                               mlp_dims=(8,), behavior_len=6),
    "dien": RecsysConfig(name="dien-rows", model="dien", num_fields=3,
                         hash_capacity=512, embed_dim=6, mlp_dims=(8, 4),
                         behavior_len=8),
}
ROW_BATCH = 8
# 4 steps of 4 slots; under GBA (iota 1) step 1 holds a slot one step
# stale, step 3 one dispatched at step 0 that Eq. (1) drops and the per-ID
# relaxation partly rescues
ROW_STEPS = {
    "gba": [[Slot(i, 0, 0, 1.0) for i in range(4)],
            [Slot(4, 1, 1, 1.0), Slot(5, 1, 1, 1.0), Slot(6, 0, 0, 1.0),
             Slot(7, 1, 1, 1.0)],
            [Slot(8 + i, 2, 2, 1.0) for i in range(4)],
            [Slot(12, 3, 3, 1.0), Slot(13, 0, 0, 0.0), Slot(14, 3, 3, 1.0),
             Slot(15, 2, 2, 1.0)]],
    "sync": [[Slot(4 * k + i, k, k, 1.0) for i in range(4)]
             for k in range(4)],
}


def _capture_sgd(lr: float = 0.05) -> Optimizer:
    """SGD whose state is the last aggregated gradient it applied."""
    return Optimizer(
        "capture-sgd", lambda p: jax.tree.map(jnp.zeros_like, p),
        lambda p, g, s: (jax.tree.map(lambda x, y: x - lr * y, p, g), g))


def _per_slot_table_step(trainer: GBATrainer, gba: bool, m: int,
                         shared_src: bool):
    """The step as it was built before the row scatter: each slot's
    gradient of the whole table by ``vmap(grad)``, then the ``row_mask``
    masked (GBA) or ``weights`` weighted M-way sums over
    ``max(emb_cnt, 1)``."""
    cfg, cap, iota = trainer.cfg, trainer.cfg.hash_capacity, trainer.iota
    grad_fn = jax.value_and_grad(lambda p, b: R.recsys_loss(p, cfg, b))

    def step(src, params, opt_state, batches, tokens, weights, step_k,
             last_update):
        losses, grads = jax.vmap(grad_fn, in_axes=(
            None if shared_src else 0, 0))(src, batches)
        ids = jax.vmap(R.flat_ids)(batches).reshape(m, -1)
        touched01 = jax.vmap(lambda i: jnp.zeros((cap,)).at[i].add(1.0))(
            ids) > 0
        rescued = jnp.int32(0)
        if gba:
            slot_ok = (step_k - tokens) <= iota
            keep_row = jnp.where(slot_ok[:, None], 1.0,
                                 (last_update[None, :] <= tokens[:, None]
                                  ).astype(jnp.float32))
            row_mask = touched01 * keep_row
            rescued = jnp.sum((~slot_ok) & (row_mask.sum(axis=1) > 0))
            emb_cnt = row_mask.sum(axis=0)
        else:
            emb_cnt = jnp.sum(touched01 * weights[:, None], axis=0)
        cntc = jnp.maximum(emb_cnt, 1.0)
        full = {}
        for k, g in grads.items():
            if k not in R.SPARSE_KEYS:
                full[k] = jax.tree.map(
                    lambda x: jnp.tensordot(weights / m, x, axes=(0, 0)), g)
                continue
            if gba:
                num = jnp.sum(g * (row_mask[..., None] if g.ndim == 3
                                   else row_mask), axis=0)
            else:
                num = jnp.tensordot(weights, g, axes=(0, 0))
            full[k] = num / (cntc[:, None] if num.ndim > 1 else cntc)
        params, opt_state = trainer.optimizer.update(params, full, opt_state)
        last_update = jnp.where(emb_cnt > 0, step_k, last_update)
        return params, opt_state, last_update, losses, rescued

    return jax.jit(step)


@pytest.mark.parametrize("embed_stream", [None, "interpret"])
@pytest.mark.parametrize("mode", ["gba", "sync"])
@pytest.mark.parametrize("model", sorted(ROW_CFGS))
def test_row_scatter_matches_per_slot_table_gradients(model, mode,
                                                      embed_stream):
    """The step that differentiates by the gathered rows and scatters them
    once gives the aggregated ``embed``/``linear`` gradients, the replayed
    parameters and ``last_update`` of the per-slot table gradients it
    replaced, through the XLA scatter and through the streamed kernel."""
    from repro.embeddings import StreamConfig

    cfg = ROW_CFGS[model]
    stream = make_clickstream(cfg, seed=0, batches_per_day=16,
                              batch_size=ROW_BATCH)
    sched = Schedule(mode, ROW_BATCH, ROW_STEPS[mode])
    opt = _capture_sgd()

    def run(trainer):
        params = init_recsys(jax.random.PRNGKey(5), cfg)
        return trainer.replay(params, opt.init(params), sched, stream, day=0)

    new = GBATrainer(cfg, opt, iota=1, embed_stream=(
        StreamConfig(interpret=True) if embed_stream else None))
    old = GBATrainer(cfg, opt, iota=1)
    old._make_step = lambda *variant: _per_slot_table_step(old, *variant)
    p1, g1, lu1, st1 = run(new)
    p2, g2, lu2, st2 = run(old)

    # float32 sums in another order: 1e-6 of each element and, where
    # terms cancel, of the leaf's largest
    assert set(g1) >= set(R.SPARSE_KEYS) & set(p1)
    for tree1, tree2 in ((g1, g2), (p1, p2)):
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(tree1),
                                jax.tree.leaves(tree2)):
            np.testing.assert_allclose(
                a, b, rtol=1e-6, atol=1e-6 * float(jnp.max(jnp.abs(b))),
                err_msg=jax.tree_util.keystr(path))
    np.testing.assert_array_equal(lu1, lu2)
    assert st1.embed_rows_rescued == st2.embed_rows_rescued
    if mode == "gba":
        assert st1.embed_rows_rescued > 0
    assert st1.scattered_rows == sum(
        len(s) for s in sched.steps) * ROW_BATCH * R.ids_per_example(cfg)


@pytest.mark.parametrize("embed_stream", [None, "interpret"])
def test_rescued_from_factor_matches_slot_table_masks(embed_stream):
    """The rows a slot past iota keeps, counted from its (slot, id)
    factors, equal the count of the ``(M, cap)`` masks they replaced,
    step by step, on a schedule whose stale slots hold both ids untouched
    since their token and ids touched since."""
    from repro.embeddings import StreamConfig

    cfg = ROW_CFGS["deepfm"]
    cap = cfg.hash_capacity
    stream = make_clickstream(cfg, seed=0, batches_per_day=16,
                              batch_size=ROW_BATCH)
    trainer = GBATrainer(cfg, get_optimizer("sgd", 0.05), iota=1,
                         embed_stream=(StreamConfig(interpret=True)
                                       if embed_stream else None))
    calls = []
    make_step = trainer._make_step

    def recording(gba, m, shared_src):
        fn = make_step(gba, m, shared_src)

        def step(*args):
            out = fn(*args)
            calls.append((args[3], args[4], args[6], args[7], out[4]))
            return out
        return step

    trainer._make_step = recording
    params = init_recsys(jax.random.PRNGKey(5), cfg)
    trainer.replay(params, trainer.optimizer.init(params),
                   Schedule("gba", ROW_BATCH, ROW_STEPS["gba"]), stream,
                   day=0)

    mixed = 0
    for batches, tokens, step_k, last_update, rescued in calls:
        ids = np.asarray(jax.vmap(R.flat_ids)(batches)).reshape(
            len(tokens), -1)
        tokens, last_update = np.asarray(tokens), np.asarray(last_update)
        touched = np.zeros((len(tokens), cap), bool)
        touched[np.arange(len(tokens))[:, None], ids] = True
        slot_ok = (int(step_k) - tokens) <= trainer.iota
        keep_row = np.where(slot_ok[:, None], True,
                            last_update[None, :] <= tokens[:, None])
        row_mask = touched & keep_row
        assert int(rescued) == int(np.sum(~slot_ok & row_mask.any(axis=1)))
        for i in np.flatnonzero(~slot_ok):
            fresh = last_update[ids[i]] <= tokens[i]
            mixed += bool(fresh.any() and not fresh.all())
    assert mixed > 0


def _f32_result_dims(hlo: str) -> set[tuple[int, ...]]:
    """Sorted dims of every f32 array an instruction other than a
    parameter produces, in a compiled module's text."""
    out = set()
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%[\w.\-]+ = (.*?) ([a-z][a-z0-9\-]*)\(",
                     line)
        if m is None or m.group(2) == "parameter":
            continue
        for dims in re.findall(r"f32\[([0-9,]*)\]", m.group(1)):
            out.add(tuple(sorted(int(d) for d in dims.split(",") if d)))
    return out


@pytest.mark.parametrize("embed_stream", [None, "interpret"])
@pytest.mark.parametrize("gba,shared_src", [(True, True), (True, False),
                                            (False, True)])
def test_compiled_step_holds_no_per_slot_table(gba, shared_src,
                                               embed_stream):
    """No f32 buffer of a compiled step is a table per slot, (M, cap, D)
    or (M * cap, D) in any order of its axes: the sparse gradient grows
    with the step's ids, not with M copies of the table."""
    from repro.embeddings import StreamConfig

    cfg, m, lb = ROW_CFGS["deepfm"], 4, ROW_BATCH
    trainer = GBATrainer(cfg, get_optimizer("adam", 1e-3), embed_stream=(
        StreamConfig(interpret=True) if embed_stream else None))
    params = jax.eval_shape(lambda k: init_recsys(k, cfg),
                            jax.random.PRNGKey(0))
    opt = jax.eval_shape(trainer.optimizer.init, params)
    src = params if shared_src else jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((m, *x.shape), x.dtype), params)
    sds = jax.ShapeDtypeStruct
    batches = {"fields": sds((m, lb, cfg.num_fields), jnp.int32),
               "label": sds((m, lb), jnp.float32)}
    hlo = trainer._make_step(gba, m, shared_src).lower(
        src, params, opt, batches, sds((m,), jnp.int32),
        sds((m,), jnp.float32), sds((), jnp.int32),
        sds((cfg.hash_capacity,), jnp.int32)).compile().as_text()
    cap, d = cfg.hash_capacity, cfg.embed_dim
    dims = _f32_result_dims(hlo)
    assert tuple(sorted((cap, d))) in dims      # the one table gradient
    assert not dims & {tuple(sorted((m, cap, d))),
                       tuple(sorted((m * cap, d)))}
