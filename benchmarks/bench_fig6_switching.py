"""Paper Fig. 6 / Tables 6.1-6.8: continual training with mode switching.

Protocol (scaled): pretrain a base model in sync mode for ``base_days``,
then (a) switch to each compared mode for ``eval_days`` (Fig. 6 a-c),
and (b) train each mode then switch back to sync (Fig. 6 d-f).
AUC on the next day after each training day.  Claims:

  C2a  GBA's first-day AUC after switching ~= sync (no sudden drop);
  C2b  GBA >= the semi-sync baselines on average;
  C2c  pure async with the sync hyper-parameter set collapses.

:func:`run_switching` is the GATED trajectory (suite ``switching`` in
``benchmarks.run``): it runs ``repro.launch.switch_driver`` over 4
devices and reports the end-to-end switching rows — strained-cluster
``speedup_vs_sync`` (floor: may not shrink), ``switch_count`` and
``time_to_switch_steps`` (monotone: may not grow).  The sim clock is seeded-rng deterministic and independent
of jitted-step wall time, so these columns gate exactly.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np

from benchmarks.common import csv_row
from repro.configs.recsys import CRITEO_DEEPFM
from repro.core import default_setups, run_continual
from repro.data import make_clickstream
from repro.models.recsys import init_recsys
from repro.sim.cluster import ClusterSpec

ROOT = Path(__file__).resolve().parents[1]
CFG = CRITEO_DEEPFM
MODES = ["gba", "hop_bs", "bsp", "hop_bw", "async", "async_setS"]

# fixed regardless of --fast: the gated columns must match the committed
# baseline bit-for-bit, and the run is already bench-cheap (tiny demo MLP)
SWITCH_WORKERS = 4
SWITCH_BATCHES = 240


def _driver_json(plan: str) -> dict:
    """One ``switch_driver`` run (auto + forced-sync legs on the same
    plan).  Where this process sees ``SWITCH_WORKERS`` devices (a 4-chip
    host) it runs here, since this process holds the chips.  Otherwise a
    child pinned to the CPU forces that many host devices; it never needs
    the chip this process may hold.  The child's last stdout line is the
    JSON result."""
    argv = ["--workers", str(SWITCH_WORKERS),
            "--batches", str(SWITCH_BATCHES), "--plan", plan,
            "--mode", "auto", "--compare-sync", "--json"]
    if jax.device_count() >= SWITCH_WORKERS:
        from repro.launch import switch_driver
        return switch_driver.main(argv)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)          # the driver sets its own
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.switch_driver",
         "--host-devices", str(SWITCH_WORKERS), *argv],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT)
    if proc.returncode != 0:
        raise RuntimeError(
            f"switch_driver --plan {plan} failed:\n{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run_switching() -> list[str]:
    """End-to-end switching trajectory rows (suite ``switching``)."""
    rows = []
    for plan in ("strained", "quiet"):
        t0 = time.perf_counter()
        out = _driver_json(plan)
        us = (time.perf_counter() - t0) * 1e6
        derived = (f"switch_count={out['switch_count']};"
                   f"deadlocked={out['deadlocked']};"
                   f"crashes={out['crashes']};rejoins={out['rejoins']};"
                   f"sync_timeouts={out['sync_timeouts']};"
                   f"lost_tokens={out['lost_batches']};"
                   f"swaps_verified={out['swaps_verified']};"
                   f"speedup_vs_sync={out['speedup_vs_sync']:.4f}")
        if out["time_to_first_switch_steps"] is not None:
            derived += (f";time_to_switch_steps="
                        f"{out['time_to_first_switch_steps']}")
        rows.append(csv_row(f"fig6.switch_driver.{plan}", us, derived))
    return rows


def run(base_days: int = 8, eval_days: int = 3) -> list[str]:
    stream = make_clickstream(CFG, seed=0, batches_per_day=48,
                              batch_size=256,
                              num_days=base_days + 2 * eval_days + 2)
    setups = default_setups(base_global=2048)
    spec = ClusterSpec(num_workers=16, straggler_frac=0.25,
                       straggler_slowdown=5.0, jitter=0.2, seed=0)
    t0 = time.perf_counter()

    base = init_recsys(jax.random.PRNGKey(0), CFG)
    base, res0 = run_continual(base, CFG, stream, ["sync"] * base_days,
                               setups, spec, eval_batches=16)
    sync_auc = res0.auc_per_day[-1]
    rows = [csv_row("fig6.base_sync", 0.0,
                    f"auc_last={sync_auc:.4f};"
                    f"curve={'|'.join(f'{a:.4f}' for a in res0.auc_per_day)}")]

    # continued sync = the reference line
    _, res_sync = run_continual(base, CFG, stream, ["sync"] * eval_days,
                                setups, spec, eval_batches=16,
                                start_day=base_days)
    ref = res_sync.auc_per_day
    rows.append(csv_row("fig6.from_sync.sync", 0.0,
                        f"first={ref[0]:.4f};avg={np.mean(ref):.4f}"))

    from_results = {}
    for mode in MODES:
        _, res = run_continual(base, CFG, stream, [mode] * eval_days,
                               setups, spec, eval_batches=16,
                               start_day=base_days)
        from_results[mode] = res.auc_per_day
        rows.append(csv_row(
            f"fig6.from_sync.{mode}", 0.0,
            f"first={res.auc_per_day[0]:.4f};"
            f"avg={np.mean(res.auc_per_day):.4f};"
            f"drop_vs_sync={ref[0] - res.auc_per_day[0]:+.4f}"))

    # switching back: mode for eval_days then sync for eval_days
    for mode in MODES:
        p, _ = run_continual(base, CFG, stream, [mode] * eval_days,
                             setups, spec, eval_batches=16,
                             start_day=base_days)
        _, res_back = run_continual(p, CFG, stream, ["sync"] * eval_days,
                                    setups, spec, eval_batches=16,
                                    start_day=base_days + eval_days)
        rows.append(csv_row(
            f"fig6.to_sync.{mode}", 0.0,
            f"first={res_back.auc_per_day[0]:.4f};"
            f"avg={np.mean(res_back.auc_per_day):.4f}"))

    gba_first = from_results["gba"][0]
    best_base = max(np.mean(from_results[m]) for m in MODES if m != "gba")
    claims = (f"gba_first_day_gap={ref[0] - gba_first:+.4f};"
              f"gba_avg={np.mean(from_results['gba']):.4f};"
              f"best_baseline_avg={best_base:.4f};"
              f"gba_beats_baselines="
              f"{np.mean(from_results['gba']) >= best_base - 1e-4}")
    us = (time.perf_counter() - t0) * 1e6
    rows.append(csv_row("fig6.claims", us, claims))
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
