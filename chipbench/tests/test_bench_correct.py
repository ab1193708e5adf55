"""``correct`` at a size a test run holds, on the CPU: a sound run of each
cell comes out correct; its control (the reference one step below the
configuration's precision, in the program's place) and each fault a
training cell can have, planted in the program under the rest of a run,
come out not correct.  The harness's look for a chip is skipped: these
drive ``recsys_replay.run`` directly.  On one chip there is no exchange
between chips to leave out; the answer altered where it is produced is a
GBA token, which only a GBA step reads."""
import json
import time
from pathlib import Path

import pytest

from chipbench import calibrate, check
from chipbench.runners import recsys_replay as R

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = {w["name"]: w for w in BENCH["workloads"]}


def mode(cell: str) -> str:
    from chipbench.run import resolve
    return resolve(BENCH, cell)[2]["mode"]


def small(cell: str) -> tuple[dict, dict]:
    """The cell at a test's size: every width as configured, fewer rows,
    a smaller batch, fewer steps, a ring as deep as the longest check.
    The slow workers are slowed further, so
    that with the PS round trip's larger share of a small batch's time a
    slow slot still falls past iota."""
    from chipbench.run import resolve
    _, cfg, traffic = resolve(BENCH, cell)
    cfg = dict(cfg, hash_capacity=4000,
               history=traffic.get("check_steps_max", 4))
    traffic = dict(traffic, local_batch=32, day_steps=16, pool_batches=256,
                   schedule_days=3,
                   straggler_slowdown=3 * traffic["straggler_slowdown"])
    return cfg, traffic


def run(cell: str, seed: int, hook=None) -> dict:
    cfg, traffic = small(cell)
    rec = R.run(cfg, traffic, seed=seed, seconds=0.3,
                t_start=time.perf_counter(), compiles=lambda: 0,
                trainer_hook=hook)
    assert rec.steps > 0 and rec.failed == 0
    return rec.check


def gba_check_reaches_the_relaxation(cell: str, seed: int) -> dict:
    cfg, traffic = small(cell)
    c = R.Cell(cfg, traffic, seed)
    c.setup()
    c.free()
    ref = c.reference()
    last = c.check_steps[-1]
    k = len(c.check_steps) - 1
    assert any(s.weight == 0 and k - s.token > traffic["iota"]
               for s in last)
    assert len({s.dispatch_step for s in last}) > 1
    return ref["relaxed_rows"]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct(cell):
    out = run(cell, 3_000_000_019)
    assert out["correct"], out["numbers"]


@pytest.mark.parametrize(
    "cell", sorted(c for c in CELLS if mode(c) == "gba"))
def test_gba_check_covers_drop_and_relaxation(cell):
    rows = gba_check_reaches_the_relaxation(cell, 3_000_000_031)
    assert rows["kept"] > 0 and rows["left_out"] > 0, rows


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_is_not_correct(cell):
    cfg, traffic = small(cell)
    c = R.Cell(cfg, traffic, 3_000_000_023)
    c.setup()
    c.free()
    ref = c.reference()
    out = check.compare(c.reference(control=True), ref, c.names, cfg)
    assert not out["correct"], out["numbers"]


@pytest.mark.parametrize("cell,fault", sorted(
    (c, f) for c in CELLS for f, (_, modes) in calibrate.FAULTS.items()
    if mode(c) in modes))
def test_fault_is_not_correct(cell, fault):
    out = run(cell, 3_000_000_029, hook=calibrate.FAULTS[fault][0])
    assert not out["correct"], out["numbers"]
