"""Serving observability: metric registry, streaming histograms, telemetry
hooks, step timeline export, numerics monitor, structured logging."""
import json as jsonlib
import logging
import math

import jax
import numpy as np
import pytest

from repro.models.registry import get_config, model_fns, reduce_config
from repro.serve import (ContinuousEngine, Counter, Gauge, Histogram,
                         ManualClock, MetricRegistry, Telemetry,
                         parse_prometheus_text)

_rng = np.random.default_rng(7)


@pytest.fixture(scope="module")
def setup():
    cfg = reduce_config(get_config("qwen3-4b"))
    fns = model_fns(cfg)
    params = fns.init(jax.random.PRNGKey(0))
    return cfg, params


def _engine(cfg, params, tel=None, **kw):
    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 32)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_len", 48)
    return ContinuousEngine(cfg, params, telemetry=tel, **kw)


def _drive(eng, n_req=3, prompt_len=16, max_new=6, seed=3):
    rng = np.random.default_rng(seed)
    for _ in range(n_req):
        eng.submit(rng.integers(1, 100, (prompt_len,)).astype(np.int32),
                   max_new)
    return eng.run()


class TestHistogram:
    def test_quantiles_match_numpy_within_bucket_width(self):
        h = Histogram("h")
        samples = np.random.default_rng(0).lognormal(-4.0, 1.0, 5000)
        for x in samples:
            h.observe(x)
        for q in (0.50, 0.90, 0.99):
            exact = float(np.quantile(samples, q))
            # log-bucket ladder: estimate is within one 25% bucket width
            assert abs(h.quantile(q) - exact) / exact < h.growth - 1.0
        assert h.count == len(samples)
        assert h.sum == pytest.approx(samples.sum())

    def test_quantile_clamped_to_observed_extremes(self):
        h = Histogram("h")
        h.observe(3e-3)
        assert h.quantile(0.0) == h.quantile(1.0) == 3e-3
        assert h.min == h.max == 3e-3

    def test_empty_and_garbage_observations(self):
        h = Histogram("h")
        assert h.quantile(0.5) == 0.0 and h.mean == 0.0
        h.observe(-1.0)
        h.observe(math.nan)
        h.observe(math.inf)
        assert h.count == 0      # clock glitches must not poison p99
        h.observe(1e9)           # overflow bucket still counted
        assert h.count == 1 and h.quantile(0.99) == 1e9

    def test_bucket_edges_are_geometric(self):
        h = Histogram("h", lo=1e-3, growth=2.0, n_buckets=4)
        assert h.upper_edge(0) == 1e-3
        assert h.upper_edge(2) == pytest.approx(4e-3)
        assert math.isinf(h.upper_edge(len(h.counts) - 1))

    def test_merge_is_as_if_observed_here(self):
        # quantiles of the merge == quantiles of one histogram that saw
        # every sample — and both track numpy within one bucket width
        rng = np.random.default_rng(1)
        a_s = rng.lognormal(-4.0, 1.0, 3000)
        b_s = rng.lognormal(-2.0, 0.5, 2000)
        a, b, one = Histogram("h"), Histogram("h"), Histogram("h")
        for x in a_s:
            a.observe(x)
            one.observe(x)
        for x in b_s:
            b.observe(x)
            one.observe(x)
        a.merge(b)
        both = np.concatenate([a_s, b_s])
        assert a.count == one.count == len(both)
        assert a.sum == pytest.approx(one.sum)
        assert a.min == one.min and a.max == one.max
        for q in (0.5, 0.9, 0.99):
            assert a.quantile(q) == one.quantile(q)
            exact = float(np.quantile(both, q))
            assert abs(a.quantile(q) - exact) / exact < a.growth - 1.0
        # merging an empty histogram is the identity
        before = list(a.counts)
        a.merge(Histogram("h"))
        assert list(a.counts) == before

    def test_merge_rejects_mismatched_ladder(self):
        a = Histogram("h", lo=1e-6, growth=1.25)
        for bad in (Histogram("h", lo=1e-3, growth=1.25),
                    Histogram("h", lo=1e-6, growth=2.0),
                    Histogram("h", lo=1e-6, growth=1.25, n_buckets=8)):
            with pytest.raises(ValueError, match="ladder"):
                a.merge(bad)


class TestRegistry:
    def test_counter_monotonic_and_gauge_max(self):
        reg = MetricRegistry()
        c = reg.counter("c_total")
        c.inc()
        c.inc(2)
        assert c.value == 3
        with pytest.raises(ValueError):
            c.inc(-1)
        g = reg.gauge("g")
        g.set(2.0)
        g.max(1.0)
        assert g.value == 2.0
        g.max(5.0)
        assert g.value == 5.0

    def test_get_or_create_and_kind_conflict(self):
        reg = MetricRegistry()
        assert reg.counter("x") is reg.counter("x")
        with pytest.raises(ValueError):
            reg.gauge("x")
        with pytest.raises(ValueError):
            reg.counter("bad name")

    def test_prometheus_roundtrip(self):
        reg = MetricRegistry()
        reg.counter("req_total", "requests").inc(4)
        reg.gauge("pool_util").set(0.25)
        h = reg.histogram("lat_seconds", "latency")
        for x in (1e-4, 2e-3, 5e-2, 5e-2, 1e9):
            h.observe(x)
        fams = parse_prometheus_text(reg.prometheus_text())
        assert fams["req_total"]["type"] == "counter"
        assert fams["req_total"]["samples"][0][2] == 4.0
        assert fams["pool_util"]["samples"][0][2] == 0.25
        hist = fams["lat_seconds"]
        assert hist["type"] == "histogram"
        names = {s[0] for s in hist["samples"]}
        assert names == {"lat_seconds_bucket", "lat_seconds_sum",
                         "lat_seconds_count"}
        count = [s for s in hist["samples"] if s[0] == "lat_seconds_count"]
        assert count[0][2] == 5.0

    def test_parser_rejects_malformed(self):
        with pytest.raises(ValueError):
            parse_prometheus_text("this is { not a sample\n")
        # non-cumulative buckets caught
        bad = ('# TYPE h histogram\n'
               'h_bucket{le="1.0"} 5\nh_bucket{le="+Inf"} 3\n'
               'h_sum 1.0\nh_count 3\n')
        with pytest.raises(ValueError, match="cumulative"):
            parse_prometheus_text(bad)
        # +Inf bucket must equal _count
        bad = ('# TYPE h histogram\n'
               'h_bucket{le="+Inf"} 3\nh_sum 1.0\nh_count 4\n')
        with pytest.raises(ValueError, match="_count"):
            parse_prometheus_text(bad)

    def test_jsonl_sink_appends_snapshots(self, tmp_path):
        reg = MetricRegistry()
        reg.counter("n_total").inc()
        p = tmp_path / "m.jsonl"
        reg.write_jsonl(str(p), extra={"run": 1})
        reg.counter("n_total").inc()
        reg.write_jsonl(str(p), extra={"run": 2})
        lines = [jsonlib.loads(s) for s in p.read_text().splitlines()]
        assert [r["run"] for r in lines] == [1, 2]
        assert [r["metrics"]["n_total"] for r in lines] == [1.0, 2.0]

    def test_collect_aggregates_replicas(self):
        # per-replica registries folded into a front-end aggregate:
        # counters/histograms sum, gauges max, prefix filters
        reps = []
        for i in range(3):
            r = MetricRegistry()
            r.counter("serve_req_total").inc(i + 1)
            r.gauge("serve_pool_peak").set(10.0 * (i + 1))
            h = r.histogram("serve_tpot_seconds")
            for x in np.random.default_rng(i).lognormal(-4, 1, 500):
                h.observe(x)
            r.counter("other_total").inc(100)
            reps.append(r)
        agg = MetricRegistry().collect(*reps, prefix="serve_")
        assert agg.get("serve_req_total").value == 6
        assert agg.get("serve_pool_peak").value == 30.0
        assert agg.get("serve_tpot_seconds").count == 1500
        assert agg.get("other_total") is None
        # kind mismatch across replicas raises instead of silently mixing
        bad = MetricRegistry()
        bad.gauge("serve_req_total")
        with pytest.raises(ValueError):
            agg.collect(bad)


class TestTelemetryEngine:
    """End-to-end hooks on a real engine with a deterministic clock."""

    def test_lifecycle_traces_and_histograms(self, setup):
        cfg, params = setup
        tel = Telemetry(clock=ManualClock(tick=1e-4))
        eng = _engine(cfg, params, tel)
        res = _drive(eng, n_req=3, max_new=6)
        assert sorted(res) == [0, 1, 2]
        assert tel.c_submitted.value == tel.c_finished.value == 3
        assert len(tel.finished_traces) == 3 and not tel.traces
        for tr in tel.finished_traces:
            assert tr.prompt_len == 16 and tr.n_tokens == 6
            assert (tr.t_submit <= tr.t_admit <= tr.t_first_token
                    <= tr.t_finish)
            assert tr.queue_wait >= 0 and tr.ttft > 0 and tr.e2e > 0
            assert tr.tpot_mean > 0
            names = [e[0] for e in tr.events]
            assert names[0] == "submit" and names[-1] == "finish"
            assert "first_token" in names
        assert tel.quantiles("ttft")["count"] == 3
        assert tel.quantiles("e2e")["count"] == 3
        # TPOT: dispatch-time gaps between consecutive tokens per request
        assert tel.quantiles("tpot")["count"] == 3 * (6 - 1)
        assert tel.quantiles("serve_step_seconds")["count"] > 0
        with pytest.raises(KeyError):
            tel.quantiles("nope")

    def test_engine_gauges_mirror_metrics(self, setup):
        cfg, params = setup
        tel = Telemetry(clock=ManualClock(tick=1e-4))
        eng = _engine(cfg, params, tel)
        _drive(eng)
        snap = tel.registry.snapshot()
        assert snap["serve_tokens_out"] == eng.metrics.tokens_out
        assert snap["serve_prefills"] == eng.metrics.prefills
        assert snap["serve_pool_token_capacity"] == 32 * 8
        assert snap["pool_blocks_peak"] == eng.pool.stats.peak_in_use
        assert snap["cache_lookup_tokens"] == \
            eng.prefix_cache.stats.lookup_tokens

    def test_chrome_trace_is_valid_and_loadable(self, setup, tmp_path):
        cfg, params = setup
        tel = Telemetry(clock=ManualClock(tick=1e-4))
        eng = _engine(cfg, params, tel)
        _drive(eng, n_req=2)
        p = tmp_path / "trace.json"
        tel.save_chrome_trace(str(p), meta={"arch": cfg.name})
        trace = jsonlib.loads(p.read_text())
        evs = trace["traceEvents"]
        assert trace["otherData"]["arch"] == cfg.name
        assert trace["otherData"]["dropped_events"] == 0
        phases = {e["name"] for e in evs if e["ph"] == "X"}
        assert {"step", "prefill-chunk", "decode", "drain"} <= phases
        for e in evs:
            assert e["ph"] in ("X", "i", "M")
            if e["ph"] == "X":
                assert e["ts"] >= 0 and e["dur"] > 0
        # one named lane per request plus the engine lane
        lanes = {e["tid"]: e["args"]["name"] for e in evs
                 if e["ph"] == "M" and e["name"] == "thread_name"}
        assert lanes[0] == "engine"
        assert {"req 0", "req 1"} <= set(lanes.values())
        # request lifecycle instants live on that request's lane
        instants = [e for e in evs if e["ph"] == "i"]
        assert {e["tid"] for e in instants} == {1, 2}

    def test_timeline_bounded_drops_counted(self, setup):
        cfg, params = setup
        tel = Telemetry(clock=ManualClock(tick=1e-4),
                        max_timeline_events=4)
        eng = _engine(cfg, params, tel)
        _drive(eng)
        assert len(tel.timeline.events) == 4
        assert tel.timeline.dropped > 0
        assert tel.timeline.to_chrome()["otherData"]["dropped_events"] \
            == tel.timeline.dropped

    def test_prometheus_export_of_live_run(self, setup, tmp_path):
        cfg, params = setup
        tel = Telemetry(clock=ManualClock(tick=1e-4))
        eng = _engine(cfg, params, tel)
        _drive(eng)
        p = tmp_path / "metrics.prom"
        tel.save_metrics(str(p))
        fams = parse_prometheus_text(p.read_text())
        for name in ("serve_ttft_seconds", "serve_tpot_seconds",
                     "serve_e2e_seconds", "serve_queue_wait_seconds",
                     "serve_step_seconds", "serve_requests_finished_total",
                     "serve_tokens_out", "pool_blocks_peak",
                     "cache_hit_rate"):
            assert name in fams, name

    def test_telemetry_does_not_change_tokens(self, setup):
        cfg, params = setup
        eng_off = _engine(cfg, params, None)
        eng_on = _engine(cfg, params, Telemetry(numerics_every=0))
        res_off = _drive(eng_off)
        res_on = _drive(eng_on)
        for rid in res_off:
            assert res_off[rid].tokens == res_on[rid].tokens

    def test_run_reset_rerun_reports_identically(self, setup):
        cfg, params = setup
        tel = Telemetry(clock=ManualClock(tick=1e-4))
        eng = _engine(cfg, params, tel)
        _drive(eng)
        first = dataclasses_asdict(eng.metrics)
        snap1 = tel.registry.snapshot()
        traces1 = [tr.to_dict() for tr in tel.finished_traces]

        eng.reset()
        # coherent zero: engine aggregates, pool/cache stats, telemetry
        assert eng.metrics.steps == 0 and eng.metrics.tokens_out == 0
        assert eng.pool.stats.peak_in_use == 0
        assert tel.c_finished.value == 0 and not tel.finished_traces
        assert tel.registry.snapshot()["serve_ttft_seconds"]["count"] == 0

        _drive(eng)
        second = dataclasses_asdict(eng.metrics)
        snap2 = tel.registry.snapshot()
        traces2 = [tr.to_dict() for tr in tel.finished_traces]
        # wall_s accumulates from a different clock base the second time,
        # so it matches only to float rounding; everything else exactly
        assert first.pop("wall_s") == pytest.approx(second.pop("wall_s"))
        assert first == second
        assert snap1.pop("serve_wall_seconds") == \
            pytest.approx(snap2.pop("serve_wall_seconds"))
        assert snap1 == snap2
        # per-request derived latencies identical; absolute stamps (and
        # req_ids — allocation is not an aggregate) shift
        for a, b in zip(traces1, traces2):
            for k in ("prompt_len", "n_tokens", "queue_wait", "ttft",
                      "e2e", "tpot_mean", "n_preemptions"):
                assert a[k] == pytest.approx(b[k]), k

    def test_reset_refuses_with_work_in_flight(self, setup):
        cfg, params = setup
        eng = _engine(cfg, params, Telemetry(clock=ManualClock(tick=1e-4)))
        eng.submit(np.arange(1, 9, dtype=np.int32), 4)
        with pytest.raises(RuntimeError, match="in.?flight|queued"):
            eng.reset()
        eng.run()
        eng.reset()                     # fine once drained


class TestNumericsMonitor:
    def test_live_logit_error_within_paper_bound(self, setup):
        cfg, params = setup
        tel = Telemetry(clock=ManualClock(tick=1e-4), numerics_every=1,
                        numerics_max_tokens=16)
        eng = _engine(cfg, params, tel, kv_dtype="int8")
        assert eng.quantized
        _drive(eng, n_req=2)
        assert tel.c_probes.value == 2
        err = tel.registry.get("numerics_logit_error_max").value
        assert 0.0 < err <= 0.1         # PR 4's bounded-logit-error, live
        n = tel.registry.get("numerics_probe_tokens").value
        assert n == 16 and (int(n) & (int(n) - 1)) == 0   # pow2 prefix
        assert tel.registry.get("numerics_score_intmax_max").value > 0
        assert tel.registry.get("numerics_kv_amax_max").value > 0

    def test_probe_sampling_interval(self, setup):
        cfg, params = setup
        tel = Telemetry(clock=ManualClock(tick=1e-4), numerics_every=2,
                        numerics_max_tokens=16)
        eng = _engine(cfg, params, tel, kv_dtype="int8")
        _drive(eng, n_req=3)
        assert tel.c_probes.value == 2  # prefills 1 and 3 of 3

    def test_probe_noop_on_unquantized_engine(self, setup):
        cfg, params = setup
        tel = Telemetry(clock=ManualClock(tick=1e-4), numerics_every=1)
        eng = _engine(cfg, params, tel)
        assert not eng.quantized
        _drive(eng)
        assert tel.c_probes.value == 0
        assert tel.registry.get("numerics_logit_error") is None


class TestLogging:
    def _fresh(self, name):
        logging.getLogger(name).handlers.clear()
        return name

    def test_json_mode_emits_valid_json(self, capsys):
        from repro.utils.logging import get_logger
        log = get_logger(self._fresh("t.json"), json=True)
        log.info("hello %d", 7)
        out = capsys.readouterr().out.strip()
        rec = jsonlib.loads(out)
        assert rec["msg"] == "hello 7"
        assert rec["level"] == "INFO" and rec["logger"] == "t.json"
        assert "ts" in rec

    def test_no_double_emit_and_mode_switch_in_place(self, capsys):
        from repro.utils.logging import get_logger
        name = self._fresh("t.dedup")
        log = get_logger(name)
        get_logger(name)                 # second call must not re-attach
        log.info("once")
        lines = capsys.readouterr().out.strip().splitlines()
        assert len(lines) == 1 and lines[0].endswith(":: once")
        assert len(logging.getLogger(name).handlers) == 1
        log = get_logger(name, json=True)   # swap formatter, same handler
        assert len(logging.getLogger(name).handlers) == 1
        log.info("swapped")
        assert jsonlib.loads(
            capsys.readouterr().out.strip())["msg"] == "swapped"


class TestProvenance:
    def test_header_keys_and_mode(self):
        from benchmarks.provenance import provenance
        rec = provenance(mode="smoke")
        for k in ("git_commit", "timestamp_utc", "jax_version", "backend",
                  "device", "platform", "python"):
            assert k in rec, k
        assert rec["measurement_mode"] == "smoke"
        assert "measurement_mode" not in provenance()
        jsonlib.dumps(rec)               # artifact header must be JSON-able


def dataclasses_asdict(m):
    # run() stamps wall_s from the injected clock, so even it is
    # deterministic under ManualClock — the comparison stays fully strict
    import dataclasses
    return dataclasses.asdict(m)
