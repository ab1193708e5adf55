#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration file and its traffic file (``chipbench/traffic/<traffic>.json``)
are found by name, and the configuration names its runner
(``chipbench/runners/<runner>.py``).  Every metric is read from the run's
record by its own file, ``chipbench/metrics/<metric>.py``.  With
``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` the window is profiled and the result holds its per-layer
metrics, the device's busy and window seconds and a breakdown.

The last line of standard output is the JSON result; the last lines of
standard error are the numbers that decided ``correct``, each beside its
limit.  The run fails, and prints no result, off a TPU, with fewer chips
than the cell asks for, with Pallas kernels in interpret mode, on a device
kind with no row in ``chipbench/peaks.py``, or outside a checkout of the
repository.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = ROOT / "chipbench"
CACHE_DIR = ROOT / ".jax_cache"
OP_NAME_CHARS = 160   # an op's HLO text is long; its head names it
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


class Refused(Exception):
    """The run cannot measure anything here."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(bench: dict, cell: str) -> tuple[dict, dict, dict]:
    """The cell's workload entry, configuration and traffic, by name."""
    wl = next((w for w in bench["workloads"] if w["name"] == cell), None)
    if wl is None:
        raise Refused(f"no workload {cell!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    cfg = load_json(ROOT / entry["file"])
    traffic = load_json(BENCH_DIR / "traffic" / f"{wl['traffic']}.json")
    return wl, cfg, traffic


def selected_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """End-to-end metrics of the cell, or its per-layer metrics: those
    whose ``workloads`` name it, or, without the key, those that move an
    end-to-end metric the cell reports."""
    def applies(m: dict) -> bool:
        return "workloads" not in m or cell in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in names)]


def reader(name: str):
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Counts JAX traces and backend compiles (cache loads are not
    compiles) through ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.n += 1

    def __call__(self) -> int:
        return self.n


def device_info(chips: int) -> tuple[dict, object]:
    import jax
    from repro.kernels import runtime
    from chipbench import peaks
    devices = jax.devices()
    dev = devices[0]
    print(f"platform {dev.platform}  device_kind {dev.device_kind}  "
          f"device_count {len(devices)}", file=sys.stderr, flush=True)
    if dev.platform != "tpu":
        raise Refused(f"no TPU: JAX reports platform {dev.platform!r}")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX reports "
                      f"{len(devices)}")
    if runtime.interpret_mode() is not False:
        raise Refused("Pallas kernels would run in interpret mode")
    try:
        peak = peaks.peak(dev.device_kind)
    except KeyError as e:
        raise Refused(e.args[0]) from None
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}, peak


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        return run(args)
    except Refused as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1


def run(args) -> int:
    if not (ROOT / "src" / "repro").is_dir():
        raise Refused(f"{ROOT} is not a checkout of the repository "
                      f"(src/repro is missing)")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    bench = load_json(ROOT / "BENCHMARK.json")
    wl, cfg, traffic = resolve(bench, args.workload)

    # libtpu logs under /tmp unless told otherwise; a run writes nothing
    # outside its checkout and its own temporary directory
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    device, peak = device_info(wl["chips"])
    counter = CompileCounter()
    runner = importlib.import_module(f"chipbench.runners.{cfg['runner']}")

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
        if args.trace else None
    try:
        from chipbench.trace import Tracer
        rec = runner.run(cfg, traffic, seed=args.seed, seconds=args.seconds,
                         t_start=T_START, compiles=counter,
                         tracer=Tracer(trace_dir) if trace_dir else None)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    rec.chips, rec.peak = wl["chips"], peak

    metrics = {}
    for m in selected_metrics(bench, args.workload, bool(args.trace)):
        value = reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device["memory_peak_bytes"] = rec.memory_peak_bytes
    check = rec.check
    result = {"correct": check["correct"],
              "attempted": rec.steps, "failed": rec.failed,
              "metrics": metrics, "device": device}
    if rec.trace is not None:
        device["busy_s"] = rec.trace.busy_s()
        device["window_s"] = rec.trace.window_s
        result["breakdown"] = {
            "device_ops": [[name[:OP_NAME_CHARS], s] for name, s in sorted(
                rec.trace.op_seconds().items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": rec.trace.idle_gaps()[:10]}
    result["checks"] = check["numbers"]

    print(f"setup_s {rec.setup_s:.3f}  window_s {rec.window_s:.3f}  "
          f"steps {rec.steps}  kept_examples {rec.kept_examples}  "
          f"compiles_in_window {rec.window_compiles}  "
          f"memory_peak_bytes {rec.memory_peak_bytes}", file=sys.stderr)
    print(f"worst leaves {check['worst_leaf']}  left out of the change "
          f"{check['left_out']}", file=sys.stderr)
    for name, n in check["numbers"].items():
        print(f"check {name} {n['value']!r} limit {n['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
