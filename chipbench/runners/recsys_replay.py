"""Recsys replay cells: the program's ``GBATrainer.replay`` driven day
after day by the benchmark's own schedules and click stream.

Set-up, all of it in ``setup_s``: the click stream and a pool of slot
batches on the host, the day schedules, the weights and Adam state made on
the device from the seed, the checked steps (which compile, or load from
the cache, both step variants), then ``history`` warm-up steps of the
first training day, which fill the version ring to its steady size.  The
window opens inside that same ``replay`` call, at the next step, and
replays day after day with the state carried over.  The data source sees
every step begin: it opens the window, and it closes it at the first step
that begins once ``seconds`` have passed, by raising :class:`StopWindow`
out of ``replay``.  The window therefore holds whole steps, and its end
is the moment the last of them had synced.  Once it has closed and the
program's state is freed, the reference repeats the checked steps.
"""
from __future__ import annotations

import contextlib
import gc
import sys
import time
from dataclasses import dataclass, field, replace

import numpy as np

from chipbench.traffic.clickstream import ClickStream
from chipbench.traffic.cluster import ClusterSpec, Schedule, simulate


@dataclass
class Record:
    """What one run measured, for the metric readers."""
    cfg: dict
    traffic: dict
    setup_s: float = 0.0
    window_s: float = 0.0
    steps: int = 0
    kept_examples: int = 0
    all_examples: int = 0
    failed: int = 0
    window_compiles: int = 0
    memory_peak_bytes: int = 0
    chips: int = 1
    peak: object = None
    trace: object = None
    check: dict = field(default_factory=dict)


class StopWindow(Exception):
    """Raised by the data source to end the measured window."""


class PoolFeed:
    """The data source handed to ``replay``.  ``batch(day, index)`` serves
    a pool of slot batches built once in set-up, so the window does not
    time numpy's generator: pool entry ``j`` is the stream's
    ``batch(0, j)`` and ``(day, index)`` maps to entry
    ``(day * day_batches + index) % pool``.  Every ``m``-th request begins
    a global step; once armed, the feed opens and closes the window there.
    """

    def __init__(self, stream: ClickStream, pool: int, day_batches: int,
                 m: int):
        self.pool = [stream.batch(0, j) for j in range(pool)]
        self.day_batches = day_batches
        self.m = m
        self.annotate = None        # a span factory while tracing
        self.calls = 0
        self.armed = None           # (open after n steps, seconds, hooks)
        self.t0 = self.t1 = None
        self.window_steps: list = []
        self.step_begins: list = []
        self.schedule = None
        self._step_span = None

    def begin_day(self, schedule: Schedule) -> None:
        self.schedule = schedule
        self.calls = 0

    def arm(self, open_after: int, seconds: float, on_open, on_close):
        self.armed = [open_after, seconds, on_open, on_close]

    def _begin_step(self) -> None:
        k = self.calls // self.m
        now = time.perf_counter()
        if self.t0 is None:
            if self.armed[0] > 0:
                self.armed[0] -= 1
                return
            self.armed[2]()
            self.t0 = now = time.perf_counter()
        elif now - self.t0 >= self.armed[1]:
            self.t1 = now
            self.end_step()
            self.armed[3]()
            self.armed = None
            raise StopWindow
        self.end_step()
        self.step_begins.append(now)
        if self.annotate is not None:
            self._step_span = self.annotate("chipbench.step")
            self._step_span.__enter__()
        self.window_steps.append(self.schedule.steps[k])

    def end_step(self) -> None:
        """Close the span of the step in progress (spans must nest, so the
        runner calls this before a ``replay`` call's span closes)."""
        if self._step_span is not None:
            self._step_span.__exit__(None, None, None)
            self._step_span = None

    def batch(self, day: int, index: int) -> dict:
        if self.armed is not None and self.calls % self.m == 0:
            self._begin_step()
        self.calls += 1
        j = (day * self.day_batches + index) % len(self.pool)
        if self.annotate is None:
            return self.pool[j]
        with self.annotate("chipbench.feed"):
            return self.pool[j]


def make_stream(cfg: dict, traffic: dict, seed: int) -> ClickStream:
    return ClickStream(
        hash_capacity=cfg["hash_capacity"], num_fields=cfg["num_fields"],
        behavior_len=cfg["behavior_len"], seed=seed,
        zipf_a=traffic["zipf_a"], num_days=traffic["num_days"],
        batch_size=traffic["local_batch"], drift=traffic["drift"])


def slots_per_step(traffic: dict) -> int:
    return (traffic["buffer_size"] if traffic["mode"] == "gba"
            else traffic["workers"])


def day_schedule(traffic: dict, seed: int, day: int) -> Schedule:
    spec = ClusterSpec(num_workers=traffic["workers"],
                       straggler_frac=traffic["straggler_frac"],
                       straggler_slowdown=traffic["straggler_slowdown"],
                       jitter=traffic["jitter"], seed=(seed, day))
    m = slots_per_step(traffic)
    sched = simulate(spec, traffic["mode"], traffic["day_steps"] * m,
                     traffic["local_batch"], buffer_size=m,
                     iota=traffic["iota"])
    if len(sched.steps) != traffic["day_steps"] or any(
            len(s) != m for s in sched.steps):
        raise ValueError("a day schedule must hold day_steps steps of "
                         f"{m} slots")
    return sched


def check_length(schedule: Schedule, traffic: dict) -> int:
    """How many of the first day's steps the check replays: the traffic's
    ``check_steps``, and in GBA mode at least through the first step that
    holds a slot Eq. (1) drops.  That step takes the dropped slot's
    gradient from a version at least iota + 1 steps back in the ring,
    stacked with fresher ones, and its rows through the per-ID
    relaxation."""
    n = traffic["check_steps"]
    if traffic["mode"] != "gba":
        return n
    cap = traffic["check_steps_max"]
    for k, step in enumerate(schedule.steps[:cap]):
        if any(s.weight == 0 for s in step):
            return max(n, k + 1)
    raise ValueError(f"no slot is dropped in the first {cap} steps: the "
                     "check would not reach Eq. (1) or the relaxation")


def key_from_seed(seed: int):
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def init_params(cfg: dict, seed: int):
    """The weights, made on the device in one jitted call."""
    import jax
    from chipbench.reference import model_module
    mod = model_module(cfg)
    return jax.jit(lambda k: mod.init(k, cfg))(key_from_seed(seed))


def program_config(cfg: dict):
    from repro.configs.recsys import RecsysConfig
    return RecsysConfig(
        name=cfg["name"], model=cfg["model"], num_fields=cfg["num_fields"],
        hash_capacity=cfg["hash_capacity"], embed_dim=cfg["embed_dim"],
        mlp_dims=tuple(cfg["mlp_dims"]), behavior_len=cfg["behavior_len"])


def make_trainer(cfg: dict, traffic: dict):
    from repro.core.trainer import GBATrainer
    from repro.embeddings.table import StreamConfig
    from repro.optim import get_optimizer
    o = cfg["optimizer"]
    optimizer = get_optimizer(o["name"], o["lr"], b1=o["b1"], b2=o["b2"],
                              eps=o["eps"])
    trainer = GBATrainer(program_config(cfg), optimizer,
                         iota=traffic["iota"], history=cfg["history"],
                         embed_stream=StreamConfig())
    return trainer, optimizer


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Cell:
    """One recsys cell from set-up to the check.  ``trainer_hook`` lets a
    test plant a fault in the trainer before anything compiles."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, *,
                 trainer_hook=None):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.trainer_hook = trainer_hook
        self.m = slots_per_step(traffic)

    def precision(self):
        """The matmul precision the configuration states, for every call
        into the program."""
        import jax
        return jax.default_matmul_precision(self.cfg["matmul_precision"])

    def setup(self) -> None:
        """Everything before the warm-up steps."""
        with self.precision():
            self._setup()

    def _setup(self) -> None:
        import jax
        from chipbench import check
        cfg, traffic, seed = self.cfg, self.traffic, self.seed
        t = time.perf_counter()
        self.feed = PoolFeed(make_stream(cfg, traffic, seed),
                             traffic["pool_batches"],
                             traffic["day_steps"] * self.m, self.m)
        self.schedules = [day_schedule(traffic, seed, d)
                          for d in range(traffic["schedule_days"])]
        log(f"set-up: pool and schedules {time.perf_counter() - t:.2f} s")
        t = time.perf_counter()
        self.trainer, optimizer = make_trainer(cfg, traffic)
        if self.trainer_hook is not None:
            self.trainer_hook(self.trainer)
        params0 = init_params(cfg, seed)
        opt0 = jax.jit(optimizer.init)(params0)
        self.names = check.leaf_names(params0)

        # the checked steps, from the same start three times: the first
        # step alone (Adam's first moment then holds the first aggregated
        # gradient), all but the last, and all of them (the two moments
        # give the last step's gradient)
        first = self.schedules[0]
        self.check_steps = first.steps[:check_length(first, traffic)]
        n = len(self.check_steps)
        if n > cfg["history"]:
            raise ValueError(f"{n} checked steps need a ring of {n} "
                             f"versions; history is {cfg['history']}")
        b1 = cfg["optimizer"]["b1"]

        def replay(k):
            self.feed.begin_day(first)
            return self.trainer.replay(
                params0, opt0, replace(first, steps=first.steps[:k]),
                self.feed, 0)

        _, o, _, _ = replay(1)
        grad_first = check.host(check.leaf_norms(o["m"])) / (1 - b1)
        _, o, _, _ = replay(n - 1)
        m_before = o["m"]
        del o
        p, o, lu, st = replay(n)
        self.prog = {
            "losses": list(st.losses),
            "grad_norms": [grad_first, check.host(check.adam_grad_norms(
                o["m"], m_before, b1))],
            "change_norms": check.host(check.change_norms(p, params0)),
            "last_update": np.asarray(lu)}
        del m_before
        self.state = (p, o, lu)
        log(f"set-up: weights and {n} checked steps "
            f"{time.perf_counter() - t:.2f} s")

    def train(self, seconds: float, on_open, on_close, annotate=None
              ) -> dict:
        """``history`` warm-up steps, then the window, in one run of
        days.  ``on_open``/``on_close`` run as the window opens/closes."""
        from repro.core.trainer import ReplayStats
        span = annotate or (lambda name: contextlib.nullcontext())
        warm = self.cfg["history"]
        self.feed.annotate = annotate
        self.feed.arm(warm, seconds, on_open, on_close)
        p, o, lu = self.state
        self.state = None
        stats = ReplayStats()
        day = 1
        try:
            with self.precision():
                while True:
                    sched = self.schedules[day % len(self.schedules)]
                    self.feed.begin_day(sched)
                    with span("chipbench.replay"):
                        try:
                            p, o, lu, stats = self.trainer.replay(
                                p, o, sched, self.feed, day,
                                last_update=lu, stats=stats)
                        finally:
                            self.feed.end_step()
                    day += 1
        except StopWindow:
            pass
        del p, o, lu
        self.feed.annotate = None
        steps = self.feed.window_steps
        gaps = np.diff(self.feed.step_begins + [self.feed.t1])
        med = np.median(gaps)
        log(f"window step seconds: median {med:.4f}, max {gaps.max():.4f}, "
            f"{int(np.sum(gaps > 1.5 * med))} of {len(gaps)} over 1.5x the "
            "median")
        lb = self.traffic["local_batch"]
        losses = np.asarray(stats.losses[warm:warm + len(steps)],
                            np.float64)
        return {"window_s": self.feed.t1 - self.feed.t0, "steps": len(steps),
                "kept_examples": lb * sum(s.weight > 0 for step in steps
                                          for s in step),
                "all_examples": lb * self.m * len(steps),
                "failed": int(np.sum(~np.isfinite(losses)))}

    def free(self) -> None:
        self.state = None
        self.trainer = None
        gc.collect()

    def slot_batch(self, slot):
        import jax.numpy as jnp
        return {k: jnp.asarray(v)
                for k, v in self.feed.batch(0, slot.batch_index).items()}

    def reference(self, control: bool = False) -> dict:
        """The reference's readings over the checked steps, or its
        control's."""
        from chipbench import check
        from chipbench.reference import train
        params0 = init_params(self.cfg, self.seed)
        ref = train.Reference(self.cfg, self.traffic,
                              **(train.control(self.cfg) if control else {}))
        out = ref.run(params0, self.check_steps, self.slot_batch)
        return {"losses": out["losses"],
                "grad_norms": [check.host(check.leaf_norms(g))
                               for g in out["grads"]],
                "change_norms": check.host(
                    check.change_norms(out["params"], params0)),
                "last_update": np.asarray(out["last_update"]),
                "relaxed_rows": out["relaxed_rows"]}

    def check(self, ref: dict) -> dict:
        from chipbench import check
        return check.compare(self.prog, ref, self.names, self.cfg)


def run(cfg: dict, traffic: dict, *, seed: int, seconds: float,
        t_start: float, compiles, tracer=None, trainer_hook=None) -> Record:
    """Set up, measure and check one run.  ``compiles()`` counts the
    compilations so far; a ``tracer`` profiles the window."""
    import jax
    rec = Record(cfg, traffic)
    cell = Cell(cfg, traffic, seed, trainer_hook=trainer_hook)
    cell.setup()
    marks = {"gc_n": 0, "gc_s": 0.0}

    def gc_clock(phase, info):
        if info["generation"] == 2:
            if phase == "start":
                marks["gc_t"] = time.perf_counter()
            else:
                marks["gc_n"] += 1
                marks["gc_s"] += time.perf_counter() - marks["gc_t"]

    def on_open():
        # set-up's objects leave the collector's generations, so that a
        # full collection in the window scans only what the window makes
        gc.collect()
        gc.freeze()
        gc.callbacks.append(gc_clock)
        rec.setup_s = time.perf_counter() - t_start
        marks["compiles"] = compiles()
        if tracer is not None:
            tracer.start()
            # a span records only if made while the profiler runs
            marks["span"] = tracer.annotate("chipbench.window")
            marks["span"].__enter__()

    def on_close():
        rec.window_compiles = compiles() - marks["compiles"]
        gc.callbacks.remove(gc_clock)
        gc.unfreeze()
        if tracer is not None:
            marks["span"].__exit__(None, None, None)

    w = cell.train(seconds, on_open, on_close,
                   tracer.annotate if tracer else None)
    if tracer is not None:
        rec.trace = tracer.stop()
    rec.window_s, rec.steps = w["window_s"], w["steps"]
    rec.kept_examples, rec.all_examples = w["kept_examples"], \
        w["all_examples"]
    rec.failed = w["failed"]
    log(f"window: {marks['gc_n']} full collections, "
        f"{marks['gc_s']:.4f} s")
    # the runtime reserves a program's temporaries apart from the buffers
    # in use; the chip holds both at once
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    rec.memory_peak_bytes = max(s.get("peak_bytes_in_use", 0)
                                + s.get("peak_bytes_reserved", 0)
                                for s in stats)
    log(f"memory_stats {stats}")
    cell.free()
    t = time.perf_counter()
    ref = cell.reference()
    rec.check = cell.check(ref)
    log(f"reference: {time.perf_counter() - t:.2f} s, "
        f"{len(cell.check_steps)} checked steps, rows of stale slots in the "
        f"last {ref['relaxed_rows']}")
    return rec
