"""Beyond-paper ablation: staleness-decay strategies under heavy staleness.

The paper uses the hard threshold (Eq. 1) and notes other strategies are
possible.  We compare threshold / exponential / linear / no-decay on a GBA
run over a badly-strained cluster (deep staleness tail), measuring AUC
after switching from a sync base.
"""
from __future__ import annotations

import time
from dataclasses import replace

import jax
import numpy as np

from benchmarks.common import csv_row
from repro.configs.recsys import CRITEO_DEEPFM
from repro.core import default_setups, run_continual
from repro.core.trainer import GBATrainer, evaluate
from repro.data import make_clickstream
from repro.models.recsys import init_recsys
from repro.optim import get_optimizer
from repro.sim.cluster import ClusterSpec, Schedule, Slot, simulate

CFG = CRITEO_DEEPFM


def run(base_days: int = 6) -> list[str]:
    t0 = time.perf_counter()
    rows = []
    stream = make_clickstream(CFG, seed=0, batches_per_day=48,
                              batch_size=256, num_days=base_days + 3)
    setups = default_setups(base_global=2048)
    # very heavy strain -> deep staleness tail
    spec = ClusterSpec(num_workers=16, straggler_frac=0.4,
                       straggler_slowdown=12.0, jitter=0.3, seed=0)
    base = init_recsys(jax.random.PRNGKey(0), CFG)
    base, _ = run_continual(base, CFG, stream, ["sync"] * base_days, setups,
                            spec, eval_batches=12)

    sched = simulate(replace(spec, seed=99), "gba", 768, 128,
                     buffer_size=16, iota=4)
    m = sched.metrics
    rows.append(csv_row("decay.scenario", 0.0,
                        f"avg_stale={m.avg_staleness:.2f};"
                        f"max_stale={m.staleness_max};"
                        f"drops={m.dropped_batches}"))

    day = base_days

    def run_strategy(strategy: str, iota: int) -> float:
        opt = get_optimizer("adam", 6e-4)
        trainer = GBATrainer(CFG, opt, iota=iota)
        # re-weight slots per strategy (sim encodes threshold@4 weights;
        # recompute from tokens)
        from repro.core.staleness import DECAY_FNS
        import jax.numpy as jnp
        steps = []
        for k, slots in enumerate(sched.steps):
            new = []
            for s in slots:
                w = float(DECAY_FNS[strategy](
                    jnp.asarray([s.token]), jnp.int32(k), iota)[0]) \
                    if strategy != "none" else 1.0
                new.append(Slot(s.batch_index, s.token, s.dispatch_step, w))
            steps.append(new)
        sched2 = Schedule("gba", 128, steps)
        params, _, _, _ = trainer.replay(base, opt.init(base), sched2,
                                         stream, day)
        return evaluate(params, CFG, stream, day + 1, 12)

    for strategy, iota in [("threshold", 4), ("exponential", 8),
                           ("linear", 8), ("none", 10**6)]:
        auc = run_strategy(strategy, iota)
        rows.append(csv_row(f"decay.{strategy}", 0.0, f"auc={auc:.4f}"))
    us = (time.perf_counter() - t0) * 1e6
    rows.append(csv_row("decay.done", us, ""))
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
