"""End-to-end tuning-free sync<->async switching harness
(launch.switch_driver).

Fast-lane host tests cover the carryover math (pad_mask /
tree_to_flat / flat_to_tree round trips on non-tile-multiple leaves),
SwitchConfig validation, constructor geometry checks, and a 1-worker
event-driven smoke.

The slow subprocess tests are the tentpole acceptance on a forced
4-device host mesh: (a) params AND accum are bit-exact across a forced
sync->async->sync swap versus an unswitched run replaying the SAME
global-step schedule — non-tile-multiple leaves, one Eq.-(1)-decayed
slot, one tombstone slot included — with the psum sync implementation
verified to kernel tolerance plus bit-exact swap round-trips; (b) the
strained-cluster FaultPlan (25% stragglers at 4x + one transient crash)
switches sync->async within the first telemetry window, reaches >=2x
sim-clock speedup over forced-sync on the same plan, and never
deadlocks on the crashed worker (timeouts fire, the worker rejoins in
BOTH legs); (c) chaos degradations — the fallback-to-sync circuit
breaker after repeated async apply failures, telemetry-scrape dropouts
holding the mode, and compression-warmup re-entry across repeated
async entries.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.core.flat_sharded import ShardedFlatLayout
from repro.launch.switch_driver import (GlobalStep, SwitchConfig,
                                        SwitchDriver, demo_batch_fn,
                                        demo_model, demo_plan,
                                        flat_to_tree, pad_mask,
                                        tree_to_flat)
from repro.sim.cluster import ClusterSpec
from repro.sim.faults import FaultPlan

_ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
        "HOME": os.environ.get("HOME", ""), "JAX_PLATFORMS": "cpu"}
_REPO = Path(__file__).resolve().parents[1]


def _params():
    # deliberately non-tile-multiple leaf sizes vs tile=256
    k = jax.random.PRNGKey(0)
    return {"emb": jax.random.normal(k, (37, 33)),
            "mlp": {"w": jax.random.normal(jax.random.PRNGKey(1), (33,)),
                    "b": jax.random.normal(jax.random.PRNGKey(2), (7, 5))},
            "head": jax.random.normal(jax.random.PRNGKey(3), (111,))}


# ---------------------------------------------------------------------------
# carryover math (host, fast)
# ---------------------------------------------------------------------------

def test_pad_mask_marks_real_positions():
    p = _params()
    lay = ShardedFlatLayout.from_params(p, 4, tile=256,
                                        group_by=lambda n: n[0])
    mask = pad_mask(lay)
    total = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(p))
    assert mask.shape == (lay.padded_total,)
    assert float(mask.sum()) == total
    assert set(np.unique(np.asarray(mask))) <= {0.0, 1.0}


def test_tree_flat_round_trip_bit_exact():
    """tree -> flat -> tree reproduces params and accum bit-for-bit,
    and the flat accum carries initial_accum at every PAD position —
    exactly the state an unswitched fused run holds there."""
    p = _params()
    lay = ShardedFlatLayout.from_params(p, 4, tile=256,
                                        group_by=lambda n: n[0])
    accum = jax.tree.map(
        lambda l: jax.random.uniform(jax.random.PRNGKey(9), l.shape) + 0.1,
        p)
    pf, af = tree_to_flat(lay, p, accum, initial_accum=0.1)
    p2, opt2 = flat_to_tree(lay, pf, af)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p2)):
        assert jnp.array_equal(a, b)
    for a, b in zip(jax.tree.leaves(accum), jax.tree.leaves(opt2["accum"])):
        assert jnp.array_equal(a, b)
    # padding: param 0, accum exactly initial_accum
    mask = np.asarray(pad_mask(lay))
    assert np.all(np.asarray(pf)[mask == 0.0] == 0.0)
    assert np.all(np.asarray(af)[mask == 0.0] == np.float32(0.1))
    # flat -> tree -> flat also closes (f32 end to end)
    pf2, af2 = tree_to_flat(lay, p2, opt2["accum"], initial_accum=0.1)
    assert jnp.array_equal(pf, pf2) and jnp.array_equal(af, af2)


def test_accum_unravel_keeps_f32_for_bf16_params():
    """flat_to_tree must unravel the Adagrad accum as f32 even when the
    PARAM leaves are bf16 (layout.unravel would cast to leaf dtype)."""
    p = {"w": jnp.ones((300,), jnp.bfloat16)}
    lay = ShardedFlatLayout.from_params(p, 2, tile=128)
    accum = {"w": jnp.full((300,), 0.1234567, jnp.float32)}
    pf, af = tree_to_flat(lay, p, accum, initial_accum=0.1)
    _, opt = flat_to_tree(lay, pf, af)
    leaf = jax.tree.leaves(opt["accum"])[0]
    assert leaf.dtype == jnp.float32
    assert jnp.array_equal(leaf, accum["w"])


def test_switch_config_validation():
    with pytest.raises(ValueError):
        SwitchConfig(sync_impl="allreduce")
    with pytest.raises(ValueError):
        SwitchConfig(local_batch=0)
    with pytest.raises(ValueError):
        SwitchConfig(decide_every=0)
    with pytest.raises(ValueError):
        SwitchConfig(breaker_threshold=0)
    with pytest.raises(ValueError):
        SwitchConfig(max_retries=-1)
    assert SwitchConfig().push_timeout is None      # auto-resolved


def test_demo_plan_strained_shape():
    plan = demo_plan("strained", 4)
    assert len(plan.straggler_workers()) == 1       # 25% of 4
    assert len(plan.crashes) == 1
    with pytest.raises(ValueError):
        demo_plan("hurricane", 4)


# ---------------------------------------------------------------------------
# driver geometry + 1-worker smoke (host, fast)
# ---------------------------------------------------------------------------

def _driver_1w(cfg=None, plan=None, spec=None):
    mesh = make_mesh((1,), ("data",))
    params, loss_fn, group_by = demo_model()
    cfg = cfg or SwitchConfig(local_batch=8, sync_impl="fused")
    return SwitchDriver(
        mesh, loss_fn, params,
        spec=spec or ClusterSpec(num_workers=1, jitter=0.0, seed=0),
        plan=plan or FaultPlan.quiet(1), cfg=cfg,
        batch_fn=demo_batch_fn(cfg.local_batch), group_by=group_by,
        tile=128)


def test_driver_rejects_mismatched_workers():
    mesh = make_mesh((1,), ("data",))
    params, loss_fn, group_by = demo_model()
    with pytest.raises(ValueError):
        SwitchDriver(mesh, loss_fn, params,
                     spec=ClusterSpec(num_workers=2),
                     plan=FaultPlan.quiet(2),
                     cfg=SwitchConfig(local_batch=8, sync_impl="fused"),
                     batch_fn=demo_batch_fn(8), group_by=group_by,
                     tile=128)


def test_driver_rejects_bad_batch_fn():
    """batch_fn yielding a different leading dim than cfg.local_batch."""
    mesh = make_mesh((1,), ("data",))
    params, loss_fn, group_by = demo_model()
    with pytest.raises(ValueError):
        SwitchDriver(mesh, loss_fn, params,
                     spec=ClusterSpec(num_workers=1, jitter=0.0, seed=0),
                     plan=FaultPlan.quiet(1),
                     cfg=SwitchConfig(local_batch=16, sync_impl="fused"),
                     batch_fn=demo_batch_fn(8), group_by=group_by,
                     tile=128)


def test_one_worker_auto_smoke():
    """1-worker quiet cluster: speedup is exactly 1.0, so auto mode
    never leaves sync; the run drains every batch and measures."""
    drv = _driver_1w()
    res = drv.run(6, mode="auto", seed=0)
    assert res.num_global_steps == 6
    assert res.switch_count == 0 and res.mode_steps == {"sync": 6}
    assert res.samples == 6 * 8 and res.qps > 0
    assert all(np.isfinite(l) for l in res.losses)
    assert res.controller_summary is not None


def test_run_rejects_unknown_mode_and_bad_schedule():
    drv = _driver_1w()
    with pytest.raises(ValueError):
        drv.run(2, mode="warp")
    with pytest.raises(ValueError):
        drv.run_schedule([GlobalStep((0,), (0,))], ["sync", "gba"])
    with pytest.raises(ValueError):
        drv.run_schedule([GlobalStep((0, 0), (0, 1))], ["sync"])


def test_switching_bench_without_jax_platforms(monkeypatch):
    """The switching suite decides from the devices this process sees,
    not from JAX_PLATFORMS: with one CPU device and the variable unset it
    still runs, through a child that forces 4 host devices."""
    monkeypatch.syspath_prepend(str(_REPO))
    from benchmarks import bench_fig6_switching as fig6
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(fig6, "SWITCH_BATCHES", 32)
    assert jax.device_count() < fig6.SWITCH_WORKERS
    rows = fig6.run_switching()
    assert [r.split(",")[0] for r in rows] == [
        "fig6.switch_driver.strained", "fig6.switch_driver.quiet"]
    assert all("speedup_vs_sync=" in r for r in rows)


# ---------------------------------------------------------------------------
# slow: 4-device swap parity (subprocess)
# ---------------------------------------------------------------------------

_PARITY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
import numpy as np
from repro.launch.switch_driver import (SwitchDriver, SwitchConfig,
                                        GlobalStep, demo_model,
                                        demo_batch_fn)
from repro.sim.cluster import ClusterSpec
from repro.sim.faults import FaultPlan

out = {"devices": jax.device_count()}
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("data",))
params, loss_fn, group_by = demo_model()
spec = ClusterSpec(num_workers=4)
plan = FaultPlan.quiet(4)

# 8-step schedule; step 5 carries an Eq.-(1)-decayed slot (token 0 at
# gstep 5, staleness > iota) AND a tombstone slot (batch -1)
IOTA = 4
steps, b = [], 0
for k in range(8):
    toks, bats = [k] * 4, []
    for s in range(4):
        bats.append(b); b += 1
    if k == 5:
        toks[1] = 0
        toks[2] = k - IOTA - 1; bats[2] = -1
    steps.append(GlobalStep(tuple(toks), tuple(bats)))
MODES_SW = ["sync"] * 3 + ["gba"] * 3 + ["sync"] * 2

def build(sync_impl):
    cfg = SwitchConfig(local_batch=8, iota=IOTA, sync_impl=sync_impl)
    return SwitchDriver(mesh, loss_fn, params, spec=spec, plan=plan,
                        cfg=cfg, batch_fn=demo_batch_fn(8),
                        group_by=group_by)

drv = build("fused")
r_sw = drv.run_schedule(steps, MODES_SW)
r_un_gba = drv.run_schedule(steps, ["gba"] * 8)
r_un_sync = drv.run_schedule(steps, ["sync"] * 8)
out["fused_switches"] = r_sw.switch_count
out["fused_dropped"] = r_sw.dropped_batches
out["fused_tombstones"] = r_sw.tombstones
out["p_bitexact_vs_gba"] = bool(
    np.array_equal(r_sw.param_flat, r_un_gba.param_flat))
out["a_bitexact_vs_gba"] = bool(
    np.array_equal(r_sw.accum_flat, r_un_gba.accum_flat))
out["p_bitexact_vs_sync"] = bool(
    np.array_equal(r_sw.param_flat, r_un_sync.param_flat))
out["a_bitexact_vs_sync"] = bool(
    np.array_equal(r_sw.accum_flat, r_un_sync.accum_flat))
out["losses_match"] = bool(np.allclose(r_sw.losses, r_un_gba.losses,
                                       rtol=0, atol=0))

# psum sync impl: every swap round-trips bit-exactly (verify_swap
# raises otherwise) and the end state matches the fused oracle to
# kernel tolerance (XLA psum vs sequential kernel sum: last-ulp)
drv2 = build("psum")
r2 = drv2.run_schedule(steps, MODES_SW)
out["psum_swaps_verified"] = r2.swaps_verified
out["psum_param_dev"] = float(
    np.max(np.abs(r2.param_flat - r_sw.param_flat)))
out["psum_accum_dev"] = float(
    np.max(np.abs(r2.accum_flat - r_sw.accum_flat)))
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def parity_results():
    out = subprocess.run(
        [sys.executable, "-c", _PARITY_SCRIPT], capture_output=True,
        text=True, env=dict(_ENV), cwd=_REPO, timeout=540)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_swap_bit_exact_vs_unswitched(parity_results):
    """Acceptance: forced sync->async->sync swaps on the fused state are
    bit-exact against BOTH unswitched replays of the same schedule —
    params, accum, and every per-step loss — including the decayed slot,
    the tombstone, and the non-tile-multiple leaves."""
    r = parity_results
    assert r["devices"] == 4
    assert r["fused_switches"] == 2
    assert r["fused_dropped"] == 1 and r["fused_tombstones"] == 1
    assert r["p_bitexact_vs_gba"] and r["a_bitexact_vs_gba"]
    assert r["p_bitexact_vs_sync"] and r["a_bitexact_vs_sync"]
    assert r["losses_match"]


@pytest.mark.slow
def test_psum_sync_impl_swaps_verified(parity_results):
    """The pytree-psum sync implementation: both swap directions
    round-trip bit-exactly (verified in-driver), and the final state
    agrees with the fused oracle to float32 kernel tolerance."""
    r = parity_results
    assert r["psum_swaps_verified"] == 2
    assert r["psum_param_dev"] < 1e-5
    assert r["psum_accum_dev"] < 1e-5


# ---------------------------------------------------------------------------
# slow: strained-cluster acceptance through the CLI (subprocess)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def strained_results():
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.switch_driver",
         "--host-devices", "4", "--workers", "4", "--batches", "240",
         "--plan", "strained", "--mode", "auto", "--compare-sync",
         "--json"],
        capture_output=True, text=True, env=dict(_ENV), cwd=_REPO,
        timeout=540)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_strained_switches_within_window(strained_results):
    r = strained_results
    assert r["switch_count"] >= 1
    assert r["time_to_first_switch_steps"] <= 4     # first decision
    assert r["mode_timeline"][0][2] == "gba"
    assert r["swaps_verified"] >= 1


@pytest.mark.slow
def test_strained_speedup_at_least_2x(strained_results):
    assert strained_results["speedup_vs_sync"] >= 2.0


@pytest.mark.slow
def test_strained_no_deadlock_crash_and_rejoin(strained_results):
    """Both legs live through the transient crash: the async leg loses
    the in-flight token (Alg. 1) and sees the rejoin; the forced-sync
    leg discovers the dead worker by timeout (never hangs the barrier)
    and re-admits it after recovery.  A stalled run raises instead of
    returning, so completion itself is the no-deadlock claim."""
    r = strained_results
    assert r["deadlocked"] == 0
    assert r["crashes"] == 1 and r["rejoins"] == 1
    assert r["lost_batches"] == 1
    assert r["sync_timeouts"] >= 1 and r["sync_rejoins"] >= 1
    assert r["num_global_steps"] > 0 and r["final_loss"] is not None


# ---------------------------------------------------------------------------
# slow: chaos degradations (subprocess)
# ---------------------------------------------------------------------------

_CHAOS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np
import jax
from repro.core.compression import CompressionPolicy
from repro.launch.switch_driver import (SwitchDriver, SwitchConfig,
                                        demo_model, demo_batch_fn)
from repro.sim.cluster import ClusterSpec
from repro.sim.faults import FaultPlan, ScrapeDropout, StragglerWindow

out = {}
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("data",))
params, loss_fn, group_by = demo_model()
spec = ClusterSpec(num_workers=4, jitter=0.05, seed=0)

# (a) circuit breaker: the first 3 async applies fail -> fallback to
# sync, run still drains every batch
plan = FaultPlan(4, apply_failures=(0, 1, 2))
cfg = SwitchConfig(local_batch=8, sync_impl="fused", breaker_threshold=3)
drv = SwitchDriver(mesh, loss_fn, params, spec=spec, plan=plan, cfg=cfg,
                   batch_fn=demo_batch_fn(8), group_by=group_by)
r = drv.run(48, mode="gba", seed=0)
out["breaker_trips"] = r.breaker_trips
out["breaker_apply_failures"] = r.apply_failures
out["breaker_end_mode_steps"] = r.mode_steps
out["breaker_finished_steps"] = r.num_global_steps
out["breaker_drained"] = r.drained

# (b) scrape dropout: telemetry blind the whole run -> the controller
# holds sync even on a straggling cluster
plan2 = FaultPlan(4, stragglers=(StragglerWindow(0, 4.0),),
                  dropouts=(ScrapeDropout(0.0, float("inf")),))
drv2 = SwitchDriver(mesh, loss_fn, params, spec=spec, plan=plan2,
                    cfg=SwitchConfig(local_batch=8, sync_impl="fused"),
                    batch_fn=demo_batch_fn(8), group_by=group_by)
r2 = drv2.run(48, mode="auto", seed=0)
out["dropout_switches"] = r2.switch_count
out["dropout_dropped_scrapes"] = r2.dropped_scrapes

# (c) compression warmup re-entry: two separate async entries each
# replay warmup_steps warm steps before the compressed program
pol = CompressionPolicy(scheme="int8", warmup_steps=2)
drv3 = SwitchDriver(mesh, loss_fn, params, spec=spec,
                    plan=FaultPlan.quiet(4),
                    cfg=SwitchConfig(local_batch=8, sync_impl="fused"),
                    batch_fn=demo_batch_fn(8), group_by=group_by,
                    compress=pol)
sched = lambda g: "sync" if g < 2 or 6 <= g < 8 else "gba"
r3 = drv3.run(48, mode_schedule=sched, seed=0)
out["warm_steps"] = r3.warm_steps
out["reentry_switches"] = r3.switch_count
out["reentry_mode_steps"] = r3.mode_steps
out["reentry_finite"] = bool(all(np.isfinite(l) for l in r3.losses))
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def chaos_results():
    out = subprocess.run(
        [sys.executable, "-c", _CHAOS_SCRIPT], capture_output=True,
        text=True, env=dict(_ENV), cwd=_REPO, timeout=540)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_breaker_falls_back_to_sync(chaos_results):
    r = chaos_results
    assert r["breaker_apply_failures"] == 3
    assert r["breaker_trips"] == 1
    # the 3 failed async rounds consume 12 batches (PS write dropped,
    # gradients lost); after the trip the surviving 36 run sync — and
    # the 4 in-flight tokens at the swap are drained + requeued
    assert r["breaker_end_mode_steps"].get("gba", 0) == 0
    assert r["breaker_end_mode_steps"]["sync"] == 9
    assert r["breaker_finished_steps"] == 9
    assert r["breaker_drained"] == 4


@pytest.mark.slow
def test_scrape_dropout_holds_mode(chaos_results):
    r = chaos_results
    assert r["dropout_switches"] == 0
    assert r["dropout_dropped_scrapes"] > 0


@pytest.mark.slow
def test_compression_warmup_reentered_per_async_entry(chaos_results):
    r = chaos_results
    assert r["reentry_switches"] == 3       # sync->gba->sync->gba
    assert r["warm_steps"] == 4             # 2 warm steps per entry
    assert r["reentry_finite"]