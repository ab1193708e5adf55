#!/usr/bin/env python3
"""Record the phase probe that ``test_bench_phases.py`` reads, on a TPU.

    python3 chipbench/tests/record_phases_probe.py <out_dir> [--seconds 0.03]

A few traced steps of a small GBA DeepFM cell, run through the benchmark's
own runner (so the window holds the ``chipbench.*`` spans and the
program's ``replay.*`` spans as in a cell), and the compiled text of the
cell's step variants.  Writes into ``out_dir``:

- ``phases.xplane.pb.gz``: the window's trace;
- ``phases.hlo.txt.gz``: the variants' optimized HLO, one after the other.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

# DeepFM at its Criteo widths over a small table; 4 workers, one of them
# 10x slow, iota 1: stacked versions and dropped slots within a few steps
CFG = {"hash_capacity": 20000, "history": 8}
TRAFFIC = {"mode": "gba", "workers": 4, "buffer_size": 4, "local_batch": 128,
           "iota": 1, "straggler_frac": 0.25, "straggler_slowdown": 10.0,
           "jitter": 0.2, "zipf_a": 1.2, "drift": 0.05, "num_days": 4,
           "day_steps": 32, "pool_batches": 64, "schedule_days": 2,
           "check_steps": 3, "check_steps_max": 12}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("out_dir")
    ap.add_argument("--seconds", type=float, default=0.03)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from chipbench import phases
    from chipbench.runners import recsys_replay
    from chipbench.trace import Tracer

    if jax.devices()[0].platform != "tpu":
        print("record_phases_probe: needs a TPU", file=sys.stderr)
        return 1
    with open(ROOT / "chipbench" / "configs" / "deepfm-criteo.json") as f:
        cfg = dict(json.load(f), **CFG)
    out = Path(args.out_dir)
    out.mkdir(parents=True, exist_ok=True)
    log_dir = tempfile.mkdtemp(prefix="phases-probe-")
    try:
        rec = recsys_replay.run(cfg, TRAFFIC, seed=7, seconds=args.seconds,
                                t_start=time.perf_counter(),
                                compiles=lambda: 0, tracer=Tracer(log_dir))
        xplane = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                           recursive=True)
        with open(xplane[-1], "rb") as src, \
                gzip.open(out / "phases.xplane.pb.gz", "wb") as dst:
            shutil.copyfileobj(src, dst)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    texts = phases.compiled_texts(cfg, TRAFFIC)
    with gzip.open(out / "phases.hlo.txt.gz", "wt") as f:
        f.write("\n".join(texts))
    print(json.dumps({"steps": rec.steps, "busy_s": rec.trace.busy_s(),
                      "window_s": rec.trace.window_s,
                      "sizes": {p.name: p.stat().st_size
                                for p in out.iterdir()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
