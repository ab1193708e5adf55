#!/usr/bin/env python3
"""The readings the limits of ``correct`` are set from, for one cell.

    python3 chipbench/calibrate.py <cell> --seeds 1 2 3 ... [--fault-seeds 1 2 3]

In one process, for each seed: the cell's set-up and checked steps through
the program, the float32 reference, and the control (the reference one
step below the configuration's precision: ``reference.train.control``,
named under ``control_name``).
For each fault seed also the program with each planted fault the cell can
have (``FAULTS``): half of every slot batch left out, the loss and
gradient the mean over the other half; every GBA token one lower than the
schedule made it, in the step that reads it; or every step returning its
state unchanged, which reads 1 by the gradient and change measures and
needs no chip run.  Each reading is printed as one JSON line.  Run it on
the chip at the cell's own size; the benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def half_batch(trainer) -> None:
    """Planted fault: the loss and gradient over the first half of each
    slot's rows only."""
    full = trainer._loss_grad_fn
    trainer._loss_grad_fn = lambda p, b: full(
        p, {k: v[: v.shape[0] // 2] for k, v in b.items()})


def unchanged_state(trainer) -> None:
    """Planted fault: every step returns the state it was given."""
    import jax
    make = trainer._make_step

    def make_noop(*args):
        step = make(*args)

        def noop(src, params, opt, batches, tokens, weights, k, last):
            out = step(src, params, opt, batches, tokens, weights, k, last)
            return (params, opt, last) + tuple(out[3:])
        return jax.jit(noop)

    trainer._make_step = make_noop


def token_off_by_one(trainer) -> None:
    """Planted fault: the step reads every slot's GBA token one lower
    than the schedule made it."""
    import jax
    make = trainer._make_step

    def make_off(*args):
        step = make(*args)
        return jax.jit(lambda src, params, opt, batches, tokens, *rest:
                       step(src, params, opt, batches, tokens - 1, *rest))

    trainer._make_step = make_off


# each fault, and the traffic modes in which the program reads what it
# breaks (a sync step reads no token)
FAULTS = {"half_batch": (half_batch, ("gba", "sync")),
          "unchanged_state": (unchanged_state, ("gba", "sync")),
          "token_off_by_one": (token_off_by_one, ("gba",))}


def readings(cfg: dict, traffic: dict, seed: int, *, hook=None,
             control: bool = True) -> dict:
    from chipbench import check
    from chipbench.reference import train
    from chipbench.runners.recsys_replay import Cell
    cell = Cell(cfg, traffic, seed, trainer_hook=hook)
    cell.setup()
    cell.free()
    ref = cell.reference()
    checked = cell.check(ref)
    out = {"program": checked["numbers"], "program_by_step":
           checked["by_step"], "checked_steps": len(cell.check_steps),
           "relaxed_rows": ref["relaxed_rows"]}
    sides = {"ref": ref, "program": cell.prog}
    if control:
        out["control_name"] = train.control_name(cfg)
        sides["control"] = ctl = cell.reference(control=True)
        c = check.compare(ctl, ref, cell.names, cfg)
        out["control"], out["control_by_step"] = c["numbers"], c["by_step"]
    # per leaf and side: the first and the last step's gradient norms and
    # the change's norm
    out["leaves"] = {
        n: {side: [float(r["grad_norms"][0][i]),
                   float(r["grad_norms"][1][i]),
                   float(r["change_norms"][i])]
            for side, r in sides.items()}
        for i, n in enumerate(cell.names)}
    out["losses"] = {side: list(r["losses"]) for side, r in sides.items()}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="*", default=["half_batch"],
                    choices=sorted(FAULTS))
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    from chipbench.run import load_json, resolve
    _, cfg, traffic = resolve(load_json(ROOT / "BENCHMARK.json"), args.cell)
    print(f"device {jax.devices()[0].device_kind} x {len(jax.devices())}",
          file=sys.stderr)
    for seed in args.seeds:
        t = time.perf_counter()
        r = readings(cfg, traffic, seed)
        print(json.dumps({"cell": args.cell, "seed": seed, **r,
                          "seconds": time.perf_counter() - t}), flush=True)
    for seed in args.fault_seeds:
        for name in args.faults:
            hook, modes = FAULTS[name]
            if traffic["mode"] not in modes:
                continue
            r = readings(cfg, traffic, seed, hook=hook, control=False)
            print(json.dumps({"cell": args.cell, "seed": seed, "fault": name,
                              **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
