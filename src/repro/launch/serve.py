"""Production serving launcher: static-slot or continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --reduced \
        --engine paged --batch 8 --prompt-len 32 --max-new 16 \
        --block-size 16 --num-blocks 128

Observability (paged engine): ``--metrics-out metrics.prom`` (or
``.jsonl``) exports the metric registry, ``--trace-out trace.json`` writes
the Perfetto-loadable step timeline, ``--numerics-every N`` turns on the
int8 numerics monitor; any of these implies ``--telemetry``. See
serve/README.md "Observability" for the metric glossary.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.registry import (GRID_ARCHS, get_config, model_fns,
                                   reduce_config)
from repro.parallel.sharding import SERVE_RULES, sharding_context
from repro.serve import ContinuousEngine, ServeEngine
from repro.serve.engine import PREFILL_CHUNK
from repro.utils.logging import get_logger

log = get_logger("launch.serve")


def serving_params(cfg: ModelConfig, seed: int = 0):
    """Seeded random weights as serving holds them: every leaf is drawn
    directly in bf16, so no f32 copy of the model is ever on the device
    (qwen3-4b: 16.4 GiB in f32 against a 16 GB chip, 8.2 GiB in bf16).
    Returns the bf16-parameter config and its params. The draw is one
    jitted program: drawn leaf by leaf, every shape compiled on its own
    (100 s of a cold start on a v5e at qwen3-4b widths)."""
    cfg = cfg.replace(param_dtype="bfloat16")
    return cfg, jax.jit(model_fns(cfg).init)(jax.random.PRNGKey(seed))


def _serve_fleet(args, cfg, params, prompts, t0):
    """Serve the workload through a FleetSupervisor over N replicas:
    prefix-affinity (or round-robin) placement, step-watchdog
    supervision, journaled failover, fleet-aggregated metrics."""
    from repro.serve import (EngineGuard, FaultInjector, FaultPlan,
                             FleetSupervisor, Journal, Router, Telemetry,
                             canned_fleet_plan, replica_device)
    want_tel = bool(args.telemetry or args.metrics_out)

    def engine_factory():
        eng = ContinuousEngine(
            cfg, params, block_size=args.block_size,
            num_blocks=args.num_blocks, max_batch=args.batch,
            max_len=args.prompt_len + args.max_new,
            prefix_cache=args.prefix_cache,
            evict_policy=args.evict_policy,
            prefill_chunk=args.prefill_chunk,
            prefill_budget=args.prefill_budget,
            kv_dtype=None if args.kv_dtype == "auto" else args.kv_dtype,
            kv_tile_blocks=args.kv_tile_blocks,
            decode_split_k=args.decode_split_k,
            telemetry=Telemetry() if want_tel else None,
            guard=EngineGuard() if args.guard else None)
        eng.warmup()
        return eng

    faults = None
    if args.fleet_fault_plan:
        plan = (canned_fleet_plan() if args.fleet_fault_plan == "canned"
                else FaultPlan.load(args.fleet_fault_plan))
        faults = FaultInjector(plan)
        log.info("fleet fault injector attached: %d specs, seed %d",
                 len(plan.specs), plan.seed)
    journal = Journal(path=args.journal_out, fsync=args.journal_fsync)
    if args.resume:
        # crash recovery: snapshot warm-restore per replica, then adopt
        # every journaled request (terminal ones resolve immediately;
        # in-flight ones resubmit via the recompute contract)
        sup = FleetSupervisor.resume(
            engine_factory, args.replicas, args.resume,
            snapshot_dir=args.snapshot_dir, journal=journal,
            router=Router(args.router), faults=faults,
            step_parallel=True, snapshot_every=args.snapshot_every)
        for info in sup.restore_info:
            log.info("replica %d restore: %s (%s)", info["replica"],
                     info["mode"], info["reason"])
        log.info("resume: %d requests adopted (%d already terminal), "
                 "%d torn-tail records lost",
                 int(sup.tracker.c_recovered.value),
                 sum(1 for t in sup.tracker.requests.values()
                     if t.result is not None),
                 int(sup.tracker.c_tail_lost.value))
    else:
        engines = []
        for i in range(args.replicas):
            with jax.default_device(replica_device(i)):
                engines.append(engine_factory())
        sup = FleetSupervisor(engines, router=Router(args.router),
                              journal=journal, faults=faults,
                              step_parallel=True,
                              snapshot_dir=args.snapshot_dir,
                              snapshot_every=args.snapshot_every)
    treqs = [sup.submit(p, args.max_new, temperature=args.temperature,
                        deadline_s=args.deadline_ms / 1e3 or None,
                        ttft_budget_s=args.ttft_budget_ms / 1e3 or None)
             for p in prompts]
    sup.run_until_drained()
    dt = time.time() - t0
    tr = sup.tracker
    log.info("fleet[%dx %s, %s router]: %d completed, %d failed, "
             "%d failovers, %d placement retries in %d ticks",
             args.replicas, cfg.name, args.router,
             int(tr.c_completed.value), int(tr.c_failed.value),
             int(tr.c_failovers.value), int(tr.c_retries.value), sup.ticks)
    log.info("fleet health: crashed=%d hung=%d alive=%d",
             int(sup.c_crashed.value), int(sup.c_hung.value),
             int(sup.g_alive.value))
    for name, h in (("ttft", tr.h_ttft), ("e2e", tr.h_e2e)):
        if h.count:
            log.info("fleet %s: p50 %.1fms p99 %.1fms (n=%d)", name,
                     h.quantile(0.5) * 1e3, h.quantile(0.99) * 1e3,
                     h.count)
    events = journal.replay().replica_events
    if events:
        log.info("fleet replica events: %s",
                 [(e["event"], e["replica"], e["tick"]) for e in events])
    if args.metrics_out:
        agg = sup.collect_metrics()
        with open(args.metrics_out, "w") as f:
            f.write(agg.prometheus_text())
        log.info("fleet-aggregated metrics -> %s", args.metrics_out)
    if args.snapshot_dir:
        # final snapshot at quiescence: the next process warm-restarts
        # with the full radix tree even after a clean shutdown
        sup.save_snapshots()
        log.info("durable snapshots (%d written this run) -> %s",
                 int(sup.c_snapshots.value), args.snapshot_dir)
    if args.journal_out:
        log.info("write-ahead journal (%d records, fsync=%s) -> %s",
                 len(journal.records), args.journal_fsync,
                 args.journal_out)
    sup.close()
    rows = [list(t.result.tokens) for t in treqs]
    return rows, dt


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(GRID_ARCHS), default="qwen3-4b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--optimized", action="store_true")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--engine", choices=("static", "paged"),
                    default="static")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged engine: tokens per physical KV block")
    ap.add_argument("--num-blocks", type=int, default=128,
                    help="paged engine: physical blocks in the pool")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="paged engine: radix-tree prompt-prefix reuse on "
                         "the block pool (--no-prefix-cache disables)")
    ap.add_argument("--evict-policy", choices=("lru", "fifo"), default="lru",
                    help="prefix cache: order in which unreferenced cached "
                         "blocks are reclaimed")
    ap.add_argument("--prefill-chunk", type=int, default=PREFILL_CHUNK,
                    help="paged engine: prefill prompts this many tokens "
                         "per step through the flash-prefill kernel "
                         "(rounded up to a block multiple; chunks "
                         "interleave with decode steps so long prompts "
                         "don't stall running requests)")
    ap.add_argument("--prefill-budget", type=int, default=0,
                    help="paged engine: cap the TOTAL prefill chunk tokens "
                         "dealt per step across all requests (the oldest "
                         "prefilling request always advances), so many "
                         "concurrent long prompts can't starve decodes; "
                         "0 = one chunk per prefilling request per step")
    ap.add_argument("--kv-tile-blocks", type=int, default=None,
                    help="paged engine: pool blocks gathered per kv grid "
                         "step of the paged Pallas kernels (layout-only — "
                         "same attention, same visit order, identical "
                         "outputs); unset, decode derives it from the "
                         "pool's block shape and chunked prefill takes 1")
    ap.add_argument("--decode-split-k", type=int, default=1,
                    help="paged engine: partition each decode lane's KV "
                         "walk across this many parallel grid lanes, "
                         "merged by the associative Softermax combine — "
                         "cuts a long-context request's decode latency by "
                         "~the split factor on TPU (same attention; the "
                         "rescales are exact power-of-two shifts, the "
                         "partition sums reassociate within fp rounding — "
                         "a greedy flip needs an exact logit tie)")
    ap.add_argument("--autotune", choices=("off", "static", "per-step"),
                    default="off",
                    help="paged engine: grid autotuning from the analytic "
                         "kernel cost model (serve/kernel_costs.py). "
                         "'static' picks one (kv_tile_blocks, split_k) at "
                         "startup by modeled cost on the worst-case batch; "
                         "'per-step' re-plans every decode step from the "
                         "batch's lengths vector over the warmed-up "
                         "candidate grids (never compiles mid-serve). "
                         "--kv-tile-blocks/--decode-split-k bound the "
                         "candidate set; decisions are exported as "
                         "autotune_* metrics when --telemetry is on")
    ap.add_argument("--kv-dtype", choices=("auto", "bf16", "int8"),
                    default="auto",
                    help="paged engine KV pool storage: 'auto' follows "
                         "the config (int8 when --optimized sets "
                         "opt_int8_kv, compute dtype otherwise); 'int8' "
                         "stores K/V as int8 with per-row scales — half "
                         "the gather bytes, ~2x tokens at equal HBM — "
                         "dequantized inside the paged kernels")
    ap.add_argument("--telemetry", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="paged engine: per-request tracing + metric "
                         "registry + step timeline (serve/telemetry.py). "
                         "Defaults on when --metrics-out/--trace-out is "
                         "given, off otherwise (disabled hooks are free)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the final metric-registry state here: "
                         "*.jsonl appends one snapshot line (JSONL sink), "
                         "anything else gets Prometheus text exposition")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the step timeline as Chrome trace-event "
                         "JSON (load in chrome://tracing or Perfetto)")
    ap.add_argument("--numerics-every", type=int, default=0, metavar="N",
                    help="telemetry: audit every Nth prefill on an int8 "
                         "pool — lockstep full-precision vs int8 forward "
                         "publishing the live logit-error gauge plus "
                         "IntMax-overflow / scale-saturation counters "
                         "(0 = off)")
    ap.add_argument("--fault-plan", default=None, metavar="PATH|canned",
                    help="paged engine: attach the fault injector "
                         "(serve/faults.py) with this plan — a FaultPlan "
                         "JSON file, or the literal 'canned' for the "
                         "reference chaos plan. Attached after warmup so "
                         "the plan's step indices address serving steps")
    ap.add_argument("--fault-log", default=None, metavar="PATH",
                    help="write the fault-injection replay artifact "
                         "(plan + every injection that fired) here")
    ap.add_argument("--guard", action="store_true",
                    help="paged engine: enable the graceful-degradation "
                         "ladder (serve/guard.py) — sheds admissions, "
                         "shrinks prefill budgets, and quarantines "
                         "corrupted-KV requests as pool/numerics/queue "
                         "pressure crosses thresholds; recovers "
                         "automatically")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="paged engine: per-request end-to-end deadline; "
                         "overdue requests are cancelled (reason "
                         "'deadline'). 0 = no deadline")
    ap.add_argument("--ttft-budget-ms", type=float, default=0.0,
                    help="paged engine: per-request time-to-first-token "
                         "budget; requests that miss it are cancelled "
                         "(reason 'deadline'). 0 = no budget")
    ap.add_argument("--replicas", type=int, default=1,
                    help="paged engine: serve through a FleetSupervisor "
                         "over this many engine replicas (serve/"
                         "supervisor.py) — prefix-affinity routing, "
                         "step-watchdog supervision, journaled failover. "
                         "1 = the plain single-engine path")
    ap.add_argument("--router", choices=("affinity", "round-robin"),
                    default="affinity",
                    help="fleet placement policy: radix-cache prefix "
                         "affinity (load/budget fallback) or round-robin")
    ap.add_argument("--journal-out", default=None, metavar="PATH",
                    help="fleet: write the write-ahead request journal "
                         "(JSONL; serve/journal.py) — submit/placement/"
                         "token/terminal records, replayable post-mortem")
    ap.add_argument("--fleet-fault-plan", default=None,
                    metavar="PATH|canned",
                    help="fleet: attach the fleet fault injector — a "
                         "FaultPlan JSON file, or the literal 'canned' "
                         "for the reference replica-crash + hang plan "
                         "(serve/faults.py canned_fleet_plan)")
    ap.add_argument("--journal-fsync", choices=("none", "interval",
                                                "always"),
                    default="interval",
                    help="journal durability policy: 'always' fsyncs "
                         "every record (no tail loss, slowest), "
                         "'interval' flushes per record and fsyncs "
                         "periodically (default; bounded tail-loss "
                         "window), 'none' leaves records in stdio "
                         "buffers (fastest; a crash loses everything "
                         "unflushed). Dropped-tail records surface as "
                         "journal_tail_lost_total at recovery")
    ap.add_argument("--snapshot-dir", default=None, metavar="DIR",
                    help="fleet durability: write crash-consistent "
                         "per-replica snapshots (serve/snapshot.py — "
                         "KV pools, radix tree, scheduler queues, engine "
                         "counters; atomic tmp+rename, per-section "
                         "checksums) into this directory, plus one at "
                         "clean drain. Implies the fleet path even with "
                         "--replicas 1")
    ap.add_argument("--snapshot-every", type=int, default=0, metavar="N",
                    help="fleet durability: snapshot every N supervision "
                         "ticks (0 = only the final snapshot at drain); "
                         "each snapshot also anchors the journal so "
                         "replay cost is bounded by the suffix")
    ap.add_argument("--resume", default=None, metavar="JOURNAL",
                    help="crash recovery: rebuild the fleet from this "
                         "prior write-ahead journal (+ --snapshot-dir "
                         "snapshots when available — warm radix/pool "
                         "restore with fsck fallback to cold), adopt "
                         "every journaled request (terminal streams "
                         "resolve from the journal; in-flight ones "
                         "resubmit via the [prompt ‖ emitted] recompute "
                         "contract), then serve the new workload")
    args = ap.parse_args()
    configure_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    if args.optimized:
        cfg = cfg.with_opts(True)

    # a paged engine, and each fleet replica, lives on one device (its
    # weights are committed there); only the static engine spreads its
    # batch over a host mesh
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh() if args.engine == "static" else None)
    with sharding_context(mesh, SERVE_RULES):
        cfg, params = serving_params(cfg)
        rng = np.random.default_rng(0)
        prompts = rng.integers(1, cfg.vocab_size,
                               (args.batch, args.prompt_len)).astype(np.int32)
        t0 = time.time()
        if args.engine == "paged" and (args.replicas > 1 or
                                       args.snapshot_dir or args.resume):
            rows, dt = _serve_fleet(args, cfg, params, prompts, t0)
        elif args.engine == "paged":
            want_tel = args.telemetry if args.telemetry is not None else \
                bool(args.metrics_out or args.trace_out
                     or args.numerics_every)
            tel = None
            if want_tel:
                from repro.serve import Telemetry
                tel = Telemetry(numerics_every=args.numerics_every)
            guard = None
            if args.guard:
                from repro.serve import EngineGuard
                guard = EngineGuard()
            eng = ContinuousEngine(
                cfg, params, block_size=args.block_size,
                num_blocks=args.num_blocks, max_batch=args.batch,
                max_len=args.prompt_len + args.max_new,
                prefix_cache=args.prefix_cache,
                evict_policy=args.evict_policy,
                prefill_chunk=args.prefill_chunk,
                prefill_budget=args.prefill_budget,
                kv_dtype=None if args.kv_dtype == "auto" else args.kv_dtype,
                kv_tile_blocks=args.kv_tile_blocks,
                decode_split_k=args.decode_split_k,
                autotune=args.autotune,
                telemetry=tel, guard=guard,
                deadline_s=args.deadline_ms / 1e3 or None,
                ttft_budget_s=args.ttft_budget_ms / 1e3 or None)
            inj = None
            if args.fault_plan:
                from repro.serve import FaultInjector, FaultPlan, canned_plan
                plan = (canned_plan() if args.fault_plan == "canned"
                        else FaultPlan.load(args.fault_plan))
                inj = FaultInjector(plan)
                # after construction, before traffic: warmup() resets the
                # injector anyway, and no synthetic warmup runs here, so
                # plan step indices address serving steps directly
                eng.attach_faults(inj)
                log.info("fault injector attached: %d specs, seed %d",
                         len(plan.specs), plan.seed)
            from repro.serve import EngineSheddingError
            handles = []
            for p in prompts:
                try:
                    handles.append(eng.submit(p, args.max_new,
                                              temperature=args.temperature))
                except EngineSheddingError as e:
                    # the guard refused the front door; its hint is the
                    # minimum clean steps before a retry can succeed
                    log.warning("submit shed by guard (%s): retry after "
                                ">= %d clean engine steps", e,
                                e.retry_after_steps)
            results = eng.run()
            dt = time.time() - t0
            rows = [results[h.req_id].tokens for h in handles
                    if h.req_id in results]
            m = eng.metrics
            if inj is not None or guard is not None or args.deadline_ms \
                    or args.ttft_budget_ms:
                log.info("resilience: %d faults injected, %d retries, "
                         "%d cancelled (%d deadline, %d quarantined), "
                         "%d shed, guard=%s",
                         m.faults_injected, m.transient_retries,
                         m.cancelled, m.deadline_misses, m.quarantined,
                         m.shed,
                         eng.guard.state if eng.guard else "off")
            if inj is not None and args.fault_log:
                inj.save_log(args.fault_log)
                log.info("fault replay artifact -> %s", args.fault_log)
            log.info("kv pool[%s]: %d-token capacity in %.2f MiB "
                     "(%d blocks x %d)", eng.pool.kv_dtype,
                     eng.pool.token_capacity,
                     eng.pool.hbm_bytes / 2 ** 20, args.num_blocks,
                     args.block_size)
            log.info("pool peak=%d blocks (%.0f%% of %d), preemptions=%d",
                     eng.metrics.peak_blocks,
                     100.0 * eng.metrics.peak_blocks / args.num_blocks,
                     args.num_blocks, eng.metrics.preemptions)
            log.info("chunked prefill[%d]: %d chunks over %d prefills "
                     "(%d prompt tokens computed)",
                     eng.prefill_chunk, eng.metrics.prefill_chunks,
                     eng.metrics.prefills, eng.metrics.prefill_tokens)
            if eng.prefix_cache is not None:
                cs = eng.prefix_cache.stats
                log.info("prefix cache[%s]: hit %d/%d prompt tokens "
                         "(%.0f%%), %d shared-block peak, %d COW, "
                         "%d evictions, prefill savings %.2fx",
                         args.evict_policy, cs.hit_tokens, cs.lookup_tokens,
                         100.0 * cs.hit_rate,
                         eng.metrics.shared_blocks_peak,
                         eng.metrics.cow_copies, cs.evictions,
                         eng.metrics.prefill_savings)
            if tel is not None:
                for nm in ("ttft", "tpot", "e2e"):
                    q = tel.quantiles(nm)
                    log.info("%s: p50 %.1fms p90 %.1fms p99 %.1fms "
                             "(n=%d)", nm, q["p50"] * 1e3, q["p90"] * 1e3,
                             q["p99"] * 1e3, q["count"])
                err = tel.registry.get("numerics_logit_error_max")
                if err is not None:
                    log.info("numerics: max |full - int8| logit delta "
                             "%.4f over %d probes", err.value,
                             tel.c_probes.value)
                kd = tel.registry.get("kernel_dma_bytes_total")
                if kd is not None and kd.value > 0:
                    kw = tel.registry.get("kernel_waste_bytes_total")
                    kf = tel.registry.get("kernel_flops_total")
                    log.info("kernel cost: %.2f MiB gather DMA "
                             "(%.0f%% clamped waste), %.2f MFLOP",
                             kd.value / 2 ** 20,
                             100.0 * kw.value / kd.value,
                             kf.value / 1e6)
                if eng.planner is not None:
                    log.info("autotune[%s]: grid=(tile=%d, split=%d), "
                             "decisions %s", args.autotune,
                             eng.kv_tile_blocks, eng.decode_split_k,
                             eng.planner.summary() or "(static)")
                if args.metrics_out:
                    tel.save_metrics(args.metrics_out,
                                     extra={"arch": cfg.name,
                                            "engine": "paged"})
                    log.info("metrics -> %s", args.metrics_out)
                else:
                    # no sink requested: the run's metrics still surface —
                    # final Prometheus exposition straight to stdout
                    print("# final metric registry (Prometheus text "
                          "exposition; pass --metrics-out to write a file)")
                    print(tel.registry.prometheus_text(), end="")
                if args.trace_out:
                    tel.save_chrome_trace(args.trace_out,
                                          meta={"arch": cfg.name})
                    log.info("step timeline -> %s", args.trace_out)
        else:
            eng = ServeEngine(cfg, params,
                              max_len=args.prompt_len + args.max_new)
            res = eng.generate(prompts, args.max_new,
                               temperature=args.temperature)
            dt = time.time() - t0
            rows = [r.tolist() for r in res.tokens]
    toks = args.batch * args.max_new
    log.info("%s[%s]: %d tokens in %.2fs (%.1f tok/s incl. compile)",
             cfg.name, args.engine, toks, dt, toks / dt)
    for i, row in enumerate(rows[:2]):
        log.info("seq%d: %s", i, row)


if __name__ == "__main__":
    main()
