"""Provenance-aware bench regression gate: fresh smoke runs vs committed
baselines, same measurement mode only.

The repo carries two kinds of bench truth. The ``BENCH_*.json`` records
are FULL-workload snapshots (committed by the PR that landed each
optimization) — authoritative but expensive, and some were produced under
a different measurement mode (Pallas interpreter, analytic model, wall
clock) than whatever machine is running CI today. Comparing a fresh
*smoke* number against a committed *full* number, or an interpreter
number against a compiled one, is apples-to-oranges; that is exactly the
trap this gate refuses.

So the gate keeps its own committed baseline store, ``BENCH_trajectory.json``
(repo root): one entry per bench, recorded at SMOKE scale with an explicit
``(measurement_mode, scale)`` stamp via ``--record``. ``--check`` reruns
every bench at the recorded scale and fails on a >10% regression
(``--threshold``) **only when the fresh run's mode and scale match the
baseline's** — a mode mismatch (e.g. baseline recorded under the
interpreter, CI suddenly on TPU) demotes the entry to report-only rather
than producing a bogus verdict. Timing-kind entries get up to
``--retries`` reruns before a regression verdict sticks (smoke-scale wall
clock on shared CI runners is noisy; deterministic entries — modeled byte
ratios, token-count savings — get no such slack). The committed full
``BENCH_*.json`` headlines are cross-referenced into the report for
trend-reading but never gated across modes/scales.

    PYTHONPATH=src:. python benchmarks/bench_trajectory.py --record
    PYTHONPATH=src:. python benchmarks/bench_trajectory.py --check \
        --report trajectory_report.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def _mode_backend(prefix: str) -> str:
    import jax
    return f"{prefix}-{jax.default_backend()}"


def _mode_kernels() -> str:
    import jax
    return ("compiled-tpu" if jax.default_backend() == "tpu"
            else "pallas-interpret")


# Each entry: how to run the bench at smoke scale (returns its headline
# float), the measurement mode that number was produced under, whether it
# is wall-clock ("timing") or derived from counts/models ("deterministic"),
# and the committed full record to cross-reference (file, key) if any.
def _entries():
    from benchmarks import (autotune_bench, decode_paged_bench, fleet_bench,
                            kv_int8_bench, prefix_cache_bench,
                            resilience_bench, restore_bench,
                            serve_throughput)
    return {
        "decode_paged": {
            "run": lambda: decode_paged_bench.main(["--smoke"]),
            "metric": "ratio_per_head_over_grouped",
            "mode": _mode_kernels, "kind": "timing",
            "full": ("BENCH_decode.json", "ratio_per_head_over_grouped")},
        "kv_int8": {
            "run": lambda: kv_int8_bench.main(["--smoke"]),
            "metric": "tok_s_ratio_int8_over_bf16",
            "mode": lambda: _mode_backend("measured"), "kind": "timing",
            "full": ("BENCH_kv_int8.json", "tok_s_ratio_int8_over_bf16")},
        "prefix_cache": {
            "run": lambda: prefix_cache_bench.main(["--smoke"]),
            "metric": "ratio_cached_over_cold",
            "mode": lambda: _mode_backend("measured"), "kind": "timing",
            "full": None},
        "serve_throughput": {
            "run": lambda: serve_throughput.main(["--fast"]),
            "metric": "tok_s_ratio_paged_over_static",
            "mode": lambda: _mode_backend("measured"), "kind": "timing",
            "full": None},
        "autotune": {
            "run": lambda: autotune_bench.main(["--smoke"]),
            "metric": "ratio_best_static_over_per_step",
            "mode": lambda: "analytic-cost-model",
            "kind": "deterministic",
            "full": ("BENCH_autotune.json",
                     "ratio_best_static_over_per_step")},
        "resilience": {
            "run": lambda: resilience_bench.main(["--smoke"]),
            "metric": "tok_s_ratio_guarded_over_fault_free",
            "mode": lambda: _mode_backend("measured"), "kind": "timing",
            "full": ("BENCH_resilience.json",
                     "tok_s_ratio_guarded_over_fault_free")},
        "fleet": {
            # tokens per supervision tick (the lockstep device-parallel
            # throughput model) — tick counts are deterministic, so no
            # timing-noise retries apply
            "run": lambda: fleet_bench.main(["--smoke"]),
            "metric": "scaling_ratio_fleet_over_single",
            "mode": lambda: "tick-model", "kind": "deterministic",
            "full": ("BENCH_fleet.json",
                     "scaling_ratio_fleet_over_single")},
        "restore": {
            # prefill tokens a cold restart recomputes per token the warm
            # (snapshot-restored radix tree) restart computes — token
            # counts are deterministic, so no timing-noise retries apply
            "run": lambda: restore_bench.main(["--smoke"]),
            "metric": "cold_over_warm_prefill_tokens",
            "mode": lambda: "token-count", "kind": "deterministic",
            "full": ("BENCH_restore.json",
                     "cold_over_warm_prefill_tokens")},
    }


def _run_entry(name, ent):
    print(f"# bench_trajectory: running {name} (smoke)")
    return float(ent["run"]())


def record(args) -> int:
    from benchmarks.provenance import provenance
    # --only re-records a subset IN PLACE: untouched entries survive with
    # their original values (a full rewrite would silently drop every
    # baseline the restricted run skipped)
    entries = {}
    if args.only and os.path.exists(args.baseline):
        with open(args.baseline) as f:
            entries = json.load(f).get("entries", {})
    for name, ent in _entries().items():
        if args.only and name not in args.only:
            continue
        entries[name] = {
            "metric": ent["metric"], "value": round(_run_entry(name, ent), 4),
            "measurement_mode": ent["mode"](), "scale": "smoke",
            "kind": ent["kind"], "direction": "higher"}
    rec = {"bench": "trajectory-baselines",
           "provenance": provenance(mode="smoke"), "entries": entries}
    with open(args.baseline, "w") as f:
        json.dump(rec, f, indent=2)
        f.write("\n")
    print(f"bench_trajectory,recorded,{len(entries)},{args.baseline}")
    return 0


def check(args) -> int:
    if not os.path.exists(args.baseline):
        print(f"bench_trajectory,error,no baseline {args.baseline} "
              f"(run --record and commit it)")
        return 2
    with open(args.baseline) as f:
        base = json.load(f)
    report = {"baseline": args.baseline,
              "baseline_provenance": base.get("provenance", {}),
              "threshold": args.threshold, "entries": {},
              "full_records": {}}
    failures = []
    for name, ent in _entries().items():
        if args.only and name not in args.only:
            continue
        b = base.get("entries", {}).get(name)
        fresh_mode = ent["mode"]()
        row = {"metric": ent["metric"], "kind": ent["kind"],
               "fresh_mode": fresh_mode, "scale": "smoke"}
        if b is None:
            # new bench with no recorded baseline: report-only, the next
            # --record picks it up
            row.update(status="no-baseline",
                       fresh=round(_run_entry(name, ent), 4))
            report["entries"][name] = row
            continue
        row["baseline"] = b["value"]
        row["baseline_mode"] = b["measurement_mode"]
        if b["measurement_mode"] != fresh_mode or b.get("scale") != "smoke":
            # provenance mismatch: a verdict here would compare different
            # instruments — surface, don't gate
            row.update(status="mode-mismatch-not-gated",
                       fresh=round(_run_entry(name, ent), 4))
            report["entries"][name] = row
            continue
        tries = 1 + (args.retries if ent["kind"] == "timing" else 0)
        best, fresh = -float("inf"), 0.0
        for i in range(tries):
            fresh = _run_entry(name, ent)
            best = max(best, fresh)
            reg = (b["value"] - best) / b["value"] if b["value"] else 0.0
            if reg <= args.threshold:
                break
            if i + 1 < tries:
                print(f"# bench_trajectory: {name} regressed "
                      f"{reg * 100:.1f}% — retrying ({i + 1}/{tries - 1})")
        reg = (b["value"] - best) / b["value"] if b["value"] else 0.0
        row.update(fresh=round(best, 4), regression=round(reg, 4),
                   status="ok" if reg <= args.threshold else "REGRESSED")
        if reg > args.threshold:
            failures.append(
                f"{name}: {ent['metric']} {best:.4f} vs baseline "
                f"{b['value']:.4f} (-{reg * 100:.1f}%, mode {fresh_mode})")
        report["entries"][name] = row

    # cross-reference the committed full-workload records (never gated:
    # different scale by construction, often different mode)
    for name, ent in _entries().items():
        if not ent["full"]:
            continue
        path, key = ent["full"]
        if os.path.exists(path):
            with open(path) as f:
                rec = json.load(f)
            report["full_records"][name] = {
                "file": path, "metric": key, "value": rec.get(key),
                "measurement_mode": rec.get("provenance", {}).get(
                    "measurement_mode"), "scale": "full", "gated": False}

    if args.report:
        with open(args.report, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        print(f"bench_trajectory,report,{args.report}")
    for name, row in report["entries"].items():
        print(f"bench_trajectory,{name},{row['metric']},"
              f"fresh,{row.get('fresh', 'n/a')},baseline,"
              f"{row.get('baseline', 'n/a')},status,{row['status']}")
    if failures:
        print("bench_trajectory,FAIL," + "; ".join(failures))
        return 1
    print("bench_trajectory,ok,no same-mode regressions "
          f"> {args.threshold * 100:.0f}%")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    g = ap.add_mutually_exclusive_group(required=True)
    g.add_argument("--record", action="store_true",
                   help="run every bench at smoke scale and (re)write the "
                        "baseline store")
    g.add_argument("--check", action="store_true",
                   help="rerun at the recorded scale and fail on same-mode "
                        "regressions beyond --threshold")
    ap.add_argument("--baseline", default="BENCH_trajectory.json")
    ap.add_argument("--report", default=None, metavar="PATH",
                    help="--check: write the full comparison (incl. the "
                         "non-gated full-record cross-reference) as JSON")
    ap.add_argument("--threshold", type=float, default=0.10,
                    help="max tolerated same-mode fractional regression")
    ap.add_argument("--retries", type=int, default=2,
                    help="extra reruns granted to TIMING benches before a "
                         "regression verdict sticks (deterministic "
                         "benches get none)")
    ap.add_argument("--only", nargs="*", default=None,
                    help="restrict to these bench names")
    args = ap.parse_args(argv)
    return record(args) if args.record else check(args)


if __name__ == "__main__":
    sys.exit(main())
