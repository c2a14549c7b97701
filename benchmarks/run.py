"""Benchmark harness: one entry per paper table/figure + the roofline report.

Prints ``name,...`` CSV lines. Heavy pieces (table3 finetune proxy) accept a
--fast flag used by CI.
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()

    from benchmarks import (fig1_breakdown, fig5_sweep, roofline_report,
                            table1_bitwidth_ablation, table3_accuracy,
                            table4_efficiency)
    from benchmarks.provenance import provenance

    import json
    print("# provenance:",
          json.dumps(provenance(mode="smoke" if args.fast else "measured"),
                     sort_keys=True))

    t0 = time.time()
    print("# Table IV — unit/PE area+energy (analytical 7nm model vs paper)")
    table4_efficiency.main()

    print("# Fig 5 — PE energy vs sequence length (model + measured)")
    fig5_sweep.main()

    print("# Fig 1 — softmax runtime fraction vs sequence length (measured)")
    fig1_breakdown.main()

    print("# Table I ablation — accuracy vs bitwidths (beyond-paper)")
    table1_bitwidth_ablation.main()

    print("# Table III — softermax-aware finetuning accuracy proxy")
    if args.fast:
        print("table3,skipped(fast)")
    else:
        table3_accuracy.main()

    print("# Serving throughput — continuous batching (paged KV) vs static")
    from benchmarks import serve_throughput
    serve_throughput.main(["--fast"] if args.fast else [])

    print("# Prefix cache — radix-tree prompt reuse on the paged pool")
    from benchmarks import prefix_cache_bench
    prefix_cache_bench.main(["--smoke"] if args.fast else [])

    print("# Int8 KV pool — equal-HBM capacity + throughput vs bf16")
    from benchmarks import kv_int8_bench
    kv_int8_bench.main(["--smoke"] if args.fast else [])

    print("# Paged decode — grouped/tiled/split kernel vs per-head walk")
    from benchmarks import decode_paged_bench
    decode_paged_bench.main(["--smoke"] if args.fast else [])

    print("# Autotune — per-step grid planning vs best static schedule")
    from benchmarks import autotune_bench
    autotune_bench.main(["--smoke"] if args.fast else [])

    print("# Resilience — guarded engine under the canned fault plan")
    from benchmarks import resilience_bench
    resilience_bench.main(["--smoke"] if args.fast else [])

    print("# Fleet — multi-replica scaling, affinity routing, failover")
    from benchmarks import fleet_bench
    fleet_bench.main(["--smoke"] if args.fast else [])

    print("# Restore — SIGKILL mid-workload, snapshot warm restart")
    from benchmarks import restore_bench
    restore_bench.main(["--smoke"] if args.fast else [])

    print("# Roofline (baseline sharding) — from dry-run artifacts")
    roofline_report.main()

    import os
    if os.path.isdir("artifacts/dryrun_opt"):
        print("# Roofline (optimized: --optimized sweep, §Perf)")
        os.environ["DRYRUN_ART"] = "artifacts/dryrun_opt"
        import importlib
        importlib.reload(roofline_report)
        roofline_report.main()
        os.environ.pop("DRYRUN_ART")
        importlib.reload(roofline_report)

        print("# Perf comparison (baseline vs optimized, §Perf)")
        from benchmarks import perf_compare
        for mesh in ("16x16", "2x16x16"):
            if os.path.isdir(os.path.join("artifacts/dryrun", mesh)):
                perf_compare.main(mesh)

    print(f"# total_bench_s,{time.time() - t0:.1f}")


if __name__ == "__main__":
    main()
