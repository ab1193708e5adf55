"""The benchmark's own training schedules: a copy of the sync and GBA
schedule builders of ``repro.sim.cluster``.

A schedule lists, for every global step, the slots the parameter server
aggregates: each slot's batch index, GBA token, dispatch step and weight
(0 when Eq. (1) drops it).  The program's ``GBATrainer.replay`` reads only
those fields, so it replays these objects as it does its own.  For the same
spec and seed the steps equal ``repro.sim.cluster.simulate``'s
(``chipbench/tests/test_bench_traffic.py``).  Only the parts the cells use
are copied: no failures, no finite PS throughput, no time-varying speed.
"""
from __future__ import annotations

import heapq
from dataclasses import dataclass, field

import numpy as np


@dataclass(frozen=True)
class ClusterSpec:
    num_workers: int
    base_speed: float = 10_000.0
    straggler_frac: float = 0.0
    straggler_slowdown: float = 4.0
    jitter: float = 0.1
    allreduce_latency: float = 0.05
    ps_roundtrip: float = 0.01
    seed: int = 0

    def worker_speeds(self, rng: np.random.Generator) -> np.ndarray:
        speeds = np.full(self.num_workers, self.base_speed)
        n_slow = int(round(self.straggler_frac * self.num_workers))
        if n_slow:
            slow = rng.choice(self.num_workers, n_slow, replace=False)
            speeds[slow] = self.base_speed / self.straggler_slowdown
        return speeds

    def speed_at(self, speeds: np.ndarray, worker: int,
                 rng: np.random.Generator) -> float:
        s = speeds[worker]
        if self.jitter:
            s = s / rng.lognormal(0.0, self.jitter)
        return max(s, 1e-3)


@dataclass(frozen=True)
class Slot:
    batch_index: int
    token: int
    dispatch_step: int
    weight: float = 1.0


@dataclass
class Schedule:
    mode: str
    local_batch: int
    steps: list[list[Slot]] = field(default_factory=list)


def sync_schedule(spec: ClusterSpec, num_batches: int, local_batch: int,
                  rng: np.random.Generator) -> Schedule:
    """AR synchronous training: N workers, a barrier every step."""
    n = spec.num_workers
    speeds = spec.worker_speeds(rng)
    sched = Schedule("sync", local_batch)
    b = k = 0
    while b + n <= num_batches:
        for w in range(n):  # draws kept in the program's order
            spec.speed_at(speeds, w, rng)
        sched.steps.append([Slot(b + w, k, k) for w in range(n)])
        b += n
        k += 1
    return sched


def gba_schedule(spec: ClusterSpec, num_batches: int, local_batch: int,
                 rng: np.random.Generator, *, buffer_size: int,
                 iota: int) -> Schedule:
    """Event-driven PS in GBA mode: async pulls, a buffer of M gradients,
    tokens ``batch // M``, Eq. (1) drops a slot staler than ``iota``."""
    n = spec.num_workers
    speeds = spec.worker_speeds(rng)
    sched = Schedule("gba", local_batch)
    events: list[tuple[float, int, int, int, int]] = []
    next_batch = 0
    k = 0
    buffer: list[tuple[int, int, int]] = []

    def dispatch(w: int, now: float) -> None:
        nonlocal next_batch
        if next_batch >= num_batches:
            return
        token = next_batch // buffer_size
        dur = local_batch / spec.speed_at(speeds, w, rng) + spec.ps_roundtrip
        heapq.heappush(events, (now + dur, w, next_batch, token, k))
        next_batch += 1

    for w in range(n):
        dispatch(w, 0.0)
    while events:
        t, w, batch, token, disp = heapq.heappop(events)
        buffer.append((batch, token, disp))
        if len(buffer) >= buffer_size:
            sched.steps.append([
                Slot(bi, tok, dp, weight=0.0 if k - tok > iota else 1.0)
                for bi, tok, dp in buffer])
            buffer.clear()
            k += 1
        dispatch(w, t)
    return sched


def simulate(spec: ClusterSpec, mode: str, num_batches: int,
             local_batch: int, *, buffer_size: int = 1,
             iota: int = 4) -> Schedule:
    rng = np.random.default_rng(spec.seed)
    if mode == "sync":
        return sync_schedule(spec, num_batches, local_batch, rng)
    if mode == "gba":
        return gba_schedule(spec, num_batches, local_batch, rng,
                            buffer_size=buffer_size, iota=iota)
    raise ValueError(f"unknown mode {mode!r}")
