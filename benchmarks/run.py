"""Benchmark harness: one module per paper table/figure (+ kernels).
Prints ``name,us_per_call,derived`` CSV.

    PYTHONPATH=src python -m benchmarks.run [--only fig6,tab52] [--fast]
        [--json [PATH]] [--check [BASELINE]] [--all]

``--json`` additionally writes the kernel, switching and serving rows
(with the derived ``k=v`` columns parsed into numbers) to
``BENCH_kernels.json`` so the perf trajectory is machine-readable across
PRs.

``--check`` compares the fresh JSON rows against a committed
baseline JSON (default ``BENCH_kernels.json``) and exits non-zero on a
>5x ``us_per_call`` regression (interpret-mode wall time is load noise;
only catastrophic algorithmic blowups should trip it), any growth of a
``vmem_bytes``, ``buffer_ratio``, ``peak_gather_bytes``,
``gather_ratio``, ``bytes_on_wire``, ``compression_ratio``,
``switch_count``, ``time_to_switch_steps`` or ``freshness_lag_steps``
column, any shrink of a
``launch_ratio``, ``speedup_vs_sync`` or ``hit_rate`` column (the
end-to-end switching trajectory rows from
``bench_fig6_switching.run_switching`` and the online-serving rows from
``bench_tab52_qps.run_serving`` — sim-clock/seeded
deterministic, so they gate exactly), any change at all of an ``audit_*``
column (auditor-derived collective census / launch-meta VMEM /
quantized-wire dtype verdict / serving cache geometry and
hit-skips-kernel proof), a
baseline row that disappeared, or a fresh row missing from the baseline
(uncommitted drift: adding a bench row without regenerating and
committing the JSON fails fast) — the CI perf gate (scripts/ci.sh).
``--all`` includes rows for superseded kernels.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

JSON_SUITES = ("kernels", "switching", "serving")
# --check: max allowed us_per_call growth.  Interpret-mode wall time
# swings ~4x with container/CI load (the bench docstrings call it noise;
# the derived columns are the claims), so this only catches catastrophic
# algorithmic blowups (serialized grids, O(V) work) — the structural
# columns below are gated exactly.
US_REGRESSION = 5.0
MONOTONE_COLS = ("vmem_bytes", "buffer_ratio", "peak_gather_bytes",
                 "gather_ratio", "bytes_on_wire", "compression_ratio",
                 # end-to-end switching trajectory: more mode flaps or a
                 # later first switch on the same fault plan = regression
                 "switch_count",
                 "time_to_switch_steps",
                 # serving: the live-sync snapshot may not fall further
                 # behind the trainer on the same publish/sync plan
                 "freshness_lag_steps")          # --check: no growth at all
FLOOR_COLS = ("launch_ratio",
              # strained-cluster auto vs forced-sync, sim clock: the
              # Fig. 6 speedup claim may not shrink (deterministic —
              # seeded-rng timing, independent of jitted wall time)
              "speedup_vs_sync",
              # serving: the hot-ID cache must keep absorbing the Zipf
              # head of a seeded request stream (deterministic counters)
              "hit_rate")                        # --check: no shrink at all
# --check: must EQUAL the baseline.  Auditor-derived structural columns
# (collective census counts, launch-meta VMEM): any drift means the
# collective schedule or kernel geometry changed, which must be a
# deliberate baseline regeneration, never noise.  The serving columns:
# cache geometry (capacity * dim * 4 bytes) and the kernel-call-counter
# proof that an all-hit batch skips the streamed kernel entirely.  The
# finding-count columns (staleness-taint dataflow pass on the sharded
# apply traces, lock-discipline lint on the serving modules) are gated
# at their baseline value of 0: a raw-gradient leak or a serving race
# flips a structural column, never noise.
EXACT_COLS = ("audit_all_gather", "audit_all_to_all", "audit_vmem_bytes",
              "audit_wire_dtype", "audit_cache_bytes",
              "audit_hit_skips_kernel", "audit_flow_findings",
              "audit_race_findings")


def parse_derived(derived: str) -> dict:
    """'a=1.5;b=2e3;c=foo' -> {'a': 1.5, 'b': 2000.0, 'c': 'foo'}."""
    out: dict = {}
    for part in derived.split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            out[k] = float(v)
        except ValueError:
            out[k] = v
    return out


def rows_to_json(collected: dict[str, list[str]]) -> list[dict]:
    records = []
    for suite, rows in collected.items():
        for row in rows:
            name, us, derived = row.split(",", 2)
            records.append({
                "suite": suite,
                "name": name,
                "us_per_call": float(us),
                **parse_derived(derived),
            })
    return records


def check_records(fresh: list[dict], baseline_path: str) -> list[str]:
    """Compare fresh kernel rows to the committed baseline; return the list
    of human-readable failures (empty = gate passes).

    Superseded rows absent from a fresh default run are not counted as
    disappeared when the baseline tagged them ``status=superseded``.
    Fresh rows with no baseline entry fail too — that is uncommitted
    drift: a new bench row only clears CI once the regenerated JSON is
    committed alongside it.
    """
    try:
        with open(baseline_path) as f:
            baseline = {r["name"]: r for r in json.load(f)}
    except FileNotFoundError:
        return [f"baseline {baseline_path} not found"]
    fresh_by_name = {r["name"]: r for r in fresh}
    failures = []
    for name, cur in fresh_by_name.items():
        if name not in baseline and cur.get("status") != "superseded":
            # superseded rows only appear under --all and are skipped in
            # the committed default-run baseline on purpose
            failures.append(
                f"{name}: fresh row not in committed baseline "
                f"(regenerate and commit {baseline_path})")
    for name, base in baseline.items():
        cur = fresh_by_name.get(name)
        if cur is None:
            if base.get("status") == "superseded":
                continue
            failures.append(f"{name}: present in baseline, missing fresh")
            continue
        b_us, c_us = base.get("us_per_call", 0.0), cur.get("us_per_call", 0.0)
        if b_us > 0 and c_us > US_REGRESSION * b_us:
            failures.append(
                f"{name}: us_per_call {c_us:.1f} > {US_REGRESSION}x "
                f"baseline {b_us:.1f}")
        for col in MONOTONE_COLS:
            if col in base and isinstance(base[col], float):
                c_val = cur.get(col)
                if c_val is None:
                    failures.append(f"{name}: {col} column disappeared")
                elif c_val > base[col]:
                    failures.append(
                        f"{name}: {col} grew {base[col]:g} -> {c_val:g}")
        for col in FLOOR_COLS:
            if col in base and isinstance(base[col], float):
                c_val = cur.get(col)
                if c_val is None:
                    failures.append(f"{name}: {col} column disappeared")
                elif c_val < base[col]:
                    failures.append(
                        f"{name}: {col} shrank {base[col]:g} -> {c_val:g}")
        for col in EXACT_COLS:
            # auditor columns are floats (census counts, VMEM) or strings
            # (audit_wire_dtype); both gate on exact equality
            if col in base and isinstance(base[col], (float, str)):
                c_val = cur.get(col)
                if c_val is None:
                    failures.append(f"{name}: {col} column disappeared")
                elif c_val != base[col]:
                    failures.append(
                        f"{name}: {col} changed {base[col]} -> "
                        f"{c_val} (exact-gated auditor column)")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated substrings to select benchmarks")
    ap.add_argument("--fast", action="store_true",
                    help="reduced sizes for smoke runs")
    ap.add_argument("--json", nargs="?", const="BENCH_kernels.json",
                    default="",
                    help="write kernel/switching/serving rows as JSON "
                         "(default BENCH_kernels.json)")
    ap.add_argument("--check", nargs="?", const="BENCH_kernels.json",
                    default="",
                    help="fail on perf/footprint regressions vs a baseline "
                         "JSON (default BENCH_kernels.json)")
    ap.add_argument("--all", action="store_true",
                    help="include rows for superseded kernels")
    ap.add_argument("--summary", action="store_true",
                    help="print a one-line-per-row table of the gated "
                         "JSON rows (scripts/ci.sh)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_autoswitch, bench_convergence,
                            bench_decay_ablation,
                            bench_fig3_grad_distribution,
                            bench_fig6_switching,
                            bench_fig78_batch_ablation, bench_kernels,
                            bench_multitask, bench_tab52_qps)

    suites = [
        ("fig3", lambda: bench_fig3_grad_distribution.run(
            n_samples=8 if args.fast else 24)),
        ("fig6", lambda: bench_fig6_switching.run(
            base_days=4 if args.fast else 8,
            eval_days=2 if args.fast else 3)),
        ("tab52", lambda: bench_tab52_qps.run(
            num_batches=480 if args.fast else 1920)),
        ("fig78", lambda: bench_fig78_batch_ablation.run(
            base_days=3 if args.fast else 8,
            eval_days=1 if args.fast else 2)),
        ("convergence", bench_convergence.run),
        ("autoswitch", lambda: bench_autoswitch.run(
            num_batches=240 if args.fast else 480)),
        ("multitask", lambda: bench_multitask.run(
            base_days=3 if args.fast else 6,
            eval_days=1 if args.fast else 2)),
        ("decay", lambda: bench_decay_ablation.run(
            base_days=3 if args.fast else 6)),
        ("kernels", lambda: bench_kernels.run(all_rows=args.all)),
        # gated switching trajectory: fixed size regardless of --fast
        # (the gate compares the committed baseline exactly)
        ("switching", bench_fig6_switching.run_switching),
        # gated online-learning serving rows (V=1M hot-ID cache +
        # live param sync; seeded, pull-based sync → deterministic)
        ("serving", lambda: bench_tab52_qps.run_serving(
            num_batches=32 if args.fast else 64)),
    ]
    selected = [s for s in args.only.split(",") if s]
    print("name,us_per_call,derived")
    failures = 0
    collected: dict[str, list[str]] = {}
    for name, fn in suites:
        if selected and not any(s in name for s in selected):
            continue
        t0 = time.time()
        try:
            rows = list(fn())
            for row in rows:
                print(row)
            collected[name] = rows
            print(f"suite.{name},0.0,elapsed_s={time.time() - t0:.1f}",
                  flush=True)
        except Exception:
            failures += 1
            print(f"suite.{name},0.0,FAILED", flush=True)
            traceback.print_exc()
    records = rows_to_json(
        {k: v for k, v in collected.items() if k in JSON_SUITES})
    if args.check:
        problems = check_records(records, args.check)
        for p in problems:
            print(f"check.FAIL,0.0,{p}", flush=True)
        if problems:
            sys.exit(1)
        print(f"check.ok,0.0,baseline={args.check};rows={len(records)}",
              flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(records, f, indent=2)
        print(f"suite.json,0.0,wrote={args.json};rows={len(records)}",
              flush=True)
    if args.summary and records:
        gated = MONOTONE_COLS + FLOOR_COLS + EXACT_COLS
        print(f"{'gated row':<55} {'us/call':>10}  gated columns")
        for r in records:
            cols = " ".join(
                f"{k}={r[k]:g}" if isinstance(r[k], float) else
                f"{k}={r[k]}" for k in gated
                if isinstance(r.get(k), (float, str)))
            print(f"{r['name']:<55} {r['us_per_call']:>10.1f}  {cols}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
