"""The engine's own tracing: ``serve.*`` profiler spans at its phase
boundaries, and the counters of where a step's time goes
(``forced_syncs``, ``decode_rows``, ``admit_blocked_steps``)."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.models.registry import get_config, model_fns, reduce_config
from repro.serve import ContinuousEngine, Telemetry
from repro.serve.snapshot import apply_snapshot, snapshot_state


@pytest.fixture(scope="module")
def setup():
    cfg = reduce_config(get_config("qwen3-4b"))
    params = model_fns(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def _engine(cfg, params, **kw):
    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 32)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_len", 48)
    return ContinuousEngine(cfg, params, **kw)


def _submit(eng, lens, max_news, seed=3, temperature=0.0):
    rng = np.random.default_rng(seed)
    for n, k in zip(lens, max_news):
        eng.submit(rng.integers(1, 100, (n,)).astype(np.int32), k,
                   temperature=temperature)


def _steps(eng):
    """Steps the engine to the end; returns how many steps finished at
    least one request."""
    finishing = 0
    while eng.sched.has_work():
        before = len(eng.sched.finished)
        eng.step()
        finishing += len(eng.sched.finished) > before
    return finishing


class TestCounters:
    @pytest.mark.parametrize("chunk", [48, 8])     # whole prompts; several
    def test_forced_syncs_one_per_finishing_step(self, setup, chunk):
        cfg, params = setup
        eng = _engine(cfg, params, prefill_chunk=chunk)
        _submit(eng, [16, 12, 20, 9, 14], [3, 6, 4, 7, 2])
        finishing = _steps(eng)
        assert finishing >= 3
        assert eng.metrics.forced_syncs == finishing
        eng.drain()                      # a caller's wait is not counted
        assert eng.metrics.forced_syncs == finishing

    def test_no_forced_syncs_without_cache_greedy(self, setup):
        cfg, params = setup
        eng = _engine(cfg, params, prefix_cache=False)
        _submit(eng, [16, 12, 20], [3, 6, 4])
        eng.run()
        assert eng.metrics.forced_syncs == 0

    def test_sampling_drain_is_forced(self, setup):
        cfg, params = setup
        eng = _engine(cfg, params, prefix_cache=False)
        _submit(eng, [16, 12], [5, 5])
        _submit(eng, [10], [5], seed=4, temperature=0.8)
        eng.run()
        assert eng.metrics.forced_syncs > 0

    def test_decode_rows_sums_occupied_rows(self, setup):
        cfg, params = setup
        eng = _engine(cfg, params, prefill_chunk=8)
        rows = []
        inner = eng._decode

        def decode(*a, **k):
            rows.append(sum(r is not None for r in eng._rows))
            return inner(*a, **k)

        eng._decode = decode
        _submit(eng, [16, 12, 20, 9, 14, 30], [3, 6, 4, 7, 2, 5])
        eng.run()
        assert eng.metrics.decode_steps == len(rows)
        assert eng.metrics.decode_rows == sum(rows)
        assert sum(rows) > len(rows)     # more than one row at a time

    @pytest.mark.parametrize("num_blocks", [8, 64])
    def test_admit_blocked_steps(self, setup, num_blocks):
        """A step counts when admission stopped at the queue's head with
        a row free and room for more admissions: only the pool can have
        stopped it."""
        cfg, params = setup
        eng = _engine(cfg, params, num_blocks=num_blocks,
                      max_admit_per_step=2)
        # each trajectory needs 3 blocks of 8 tokens
        _submit(eng, [16] * 6, [8] * 6)
        blocked = 0
        admit = eng.sched.admit

        def count(max_n=None):
            nonlocal blocked
            got = admit(max_n)
            s = eng.sched
            blocked += bool(s.waiting and len(s.running) < s.max_batch
                            and len(got) < max_n)
            return got

        eng.sched.admit = count
        eng.run()
        assert eng.metrics.admit_blocked_steps == blocked
        assert eng.metrics.admit_blocked_steps <= eng.metrics.steps
        if num_blocks == 8:
            assert blocked > 0
        else:
            assert blocked == 0

    def test_counters_reset_and_survive_a_snapshot(self, setup):
        cfg, params = setup
        eng = _engine(cfg, params, num_blocks=8)
        _submit(eng, [16] * 4, [8] * 4)
        for _ in range(4):
            eng.step()
        assert eng.metrics.admit_blocked_steps > 0
        fresh = _engine(cfg, params, num_blocks=8)
        apply_snapshot(fresh, snapshot_state(eng))
        assert fresh.sched.n_admit_blocked == eng.sched.n_admit_blocked
        fresh.step()
        assert (fresh.metrics.admit_blocked_steps
                >= eng.metrics.admit_blocked_steps)
        fresh.run()
        fresh.reset()
        assert fresh.metrics.admit_blocked_steps == 0
        assert fresh.metrics.forced_syncs == fresh.metrics.decode_rows == 0

    def test_gauges_mirror_the_counters(self, setup):
        cfg, params = setup
        tel = Telemetry()
        eng = _engine(cfg, params, num_blocks=8, telemetry=tel)
        _submit(eng, [16] * 4, [8] * 4)
        eng.run()
        snap = tel.registry.snapshot()
        m = eng.metrics
        assert snap["serve_forced_syncs"] == m.forced_syncs > 0
        assert snap["serve_decode_rows"] == m.decode_rows > 0
        assert snap["serve_admit_blocked_steps"] == \
            m.admit_blocked_steps > 0


def _host_spans(log_dir):
    """(name, start, end, stats, line) of every ``serve.*`` event on the
    host plane of the trace under ``log_dir``."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("serve."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats), i))
    return out


class TestSpans:
    @pytest.mark.parametrize("chunk", [48, 8])     # whole prompts; several
    def test_phases_on_the_profilers_clock(self, setup, tmp_path, chunk):
        cfg, params = setup
        eng = _engine(cfg, params, prefill_chunk=chunk)
        _submit(eng, [16, 12, 20], [3, 6, 4])
        eng.step()                       # compile outside the trace
        jax.profiler.start_trace(str(tmp_path))
        try:
            _steps(eng)
        finally:
            jax.profiler.stop_trace()
        spans = _host_spans(str(tmp_path))
        names = {s[0] for s in spans}
        prefill = "serve.prefill_chunk"
        assert {"serve.step", "serve.admit", prefill, "serve.decode",
                "serve.sync", "serve.evict"} <= names
        steps = [s for s in spans if s[0] == "serve.step"]
        assert len(steps) == eng.metrics.steps - 1
        assert [s[3]["step_num"] for s in steps] == \
            list(range(1, eng.metrics.steps))
        # every phase lies inside one engine step on the same host line
        for name, a, b, stats, line in spans:
            if name == "serve.step":
                continue
            assert any(a >= s0 and b <= s1 and line == ln
                       for _, s0, s1, _, ln in steps), name
        syncs = [s for s in spans if s[0] == "serve.sync"]
        assert {s[3]["cause"] for s in syncs} == {"finish"}
        assert len(syncs) == eng.metrics.forced_syncs
        decodes = [s for s in spans if s[0] == "serve.decode"]
        assert all(1 <= s[3]["rows"] <= 4 and s[3]["width"] >= 1
                   for s in decodes)
        # the kernel's grid: 4 rows x ceil(width / T) tiles, each row
        # (empty ones attend 1) doing at least its first tile
        for *_, a, _ in decodes:
            assert a["tile"] == min(eng.kv_tile_blocks, a["width"])
            assert a["grid_tiles"] == 4 * -(-a["width"] // a["tile"])
            assert 4 <= a["live_tiles"] <= a["grid_tiles"]
        pre = [s for s in spans if s[0] == prefill]
        assert all(s[3]["tokens"] >= 1 for s in pre)
        assert {s[3]["req"] for s in pre} <= {0, 1, 2}

    def test_caller_drain_span(self, setup, tmp_path):
        cfg, params = setup
        eng = _engine(cfg, params, prefix_cache=False)
        _submit(eng, [16], [4])
        eng.step()
        jax.profiler.start_trace(str(tmp_path))
        try:
            eng.drain()
        finally:
            jax.profiler.stop_trace()
        syncs = [s for s in _host_spans(str(tmp_path))
                 if s[0] == "serve.sync"]
        assert [s[3]["cause"] for s in syncs] == ["caller"]
        assert eng.metrics.forced_syncs == 0
        eng.run()
