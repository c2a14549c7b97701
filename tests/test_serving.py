"""Continuous-batching serving subsystem: pool, scheduler, paged kernel,
end-to-end engine equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_decode import flash_decode
from repro.kernels.flash_decode_paged import (flash_decode_paged,
                                              paged_decode_ref)
from repro.kernels.flash_decode_paged.ref import gather_kv
from repro.models.registry import get_config, model_fns, reduce_config
from repro.serve import (ContinuousEngine, PagedKVCache, PoolExhausted,
                         Scheduler, ServeEngine)
from repro.serve.paged_step import paged_decode_step, paged_prefill_chunked

_rng = np.random.default_rng(11)


@pytest.fixture(scope="module")
def setup():
    cfg = reduce_config(get_config("qwen3-4b"))
    fns = model_fns(cfg)
    params = fns.init(jax.random.PRNGKey(0))
    return cfg, params


class TestPagedKVCache:
    def _pool(self, cfg, n=8, bs=8):
        return PagedKVCache(cfg, num_blocks=n, block_size=bs)

    def test_alloc_free_roundtrip(self, setup):
        cfg, _ = setup
        pool = self._pool(cfg)
        blocks = pool.alloc(1, 3)
        assert len(blocks) == 3 and 0 not in blocks  # block 0 reserved
        assert pool.num_free == 5 and pool.utilization == 3 / 8
        assert pool.free(1) == 3
        assert pool.num_free == 8 and pool.stats.blocks_in_use == 0

    def test_oom_raises_and_leaves_pool_consistent(self, setup):
        cfg, _ = setup
        pool = self._pool(cfg, n=4)
        pool.alloc(1, 3)
        with pytest.raises(PoolExhausted):
            pool.alloc(2, 2)
        assert pool.num_free == 1            # failed alloc took nothing
        pool.alloc(2, 1)
        with pytest.raises(PoolExhausted):
            pool.append_block(2)

    def test_blocks_for_and_tables(self, setup):
        cfg, _ = setup
        pool = self._pool(cfg, bs=8)
        assert pool.blocks_for(1) == 1
        assert pool.blocks_for(8) == 1
        assert pool.blocks_for(9) == 2
        pool.alloc(7, 2)
        t = pool.table_array([7, 99], width=4)
        assert t.shape == (2, 4)
        assert list(t[0, :2]) == pool.blocks_of(7)
        assert t[0, 2:].tolist() == [0, 0] and t[1].tolist() == [0] * 4

    def test_pool_shape_has_garbage_block(self, setup):
        cfg, _ = setup
        pool = self._pool(cfg, n=6, bs=4)
        assert pool.k.shape == (cfg.n_layers, 7, cfg.n_kv_heads, 4,
                                cfg.head_dim_)


class TestScheduler:
    def _sched(self, cfg, n_blocks=8, bs=8, max_batch=4):
        pool = PagedKVCache(cfg, num_blocks=n_blocks, block_size=bs)
        return Scheduler(pool, max_batch=max_batch, max_len=64)

    def _prompt(self, n=8):
        return _rng.integers(1, 100, (n,)).astype(np.int32)

    def test_fifo_admission_order_and_capacity(self, setup):
        cfg, _ = setup
        s = self._sched(cfg, n_blocks=3, bs=8)
        r1 = s.submit(self._prompt(16), 4)   # 2 blocks
        r2 = s.submit(self._prompt(16), 4)   # 2 blocks — won't fit
        r3 = s.submit(self._prompt(8), 4)    # would fit, but FIFO blocks it
        admitted = s.admit()
        assert [r.req_id for r in admitted] == [r1.req_id]
        assert [r.req_id for r in s.waiting] == [r2.req_id, r3.req_id]

    def test_evict_returns_blocks_and_readmits(self, setup):
        cfg, _ = setup
        s = self._sched(cfg, n_blocks=2, bs=8)
        r1 = s.submit(self._prompt(16), 1)
        r2 = s.submit(self._prompt(16), 1)
        assert len(s.admit()) == 1
        r1.n_generated = 1                   # r1 done (max_new=1)
        done = s.evict_finished()
        assert done[0].req_id == r1.req_id and s.pool.num_free == 2
        assert [r.req_id for r in s.admit()] == [r2.req_id]

    def test_admission_reserves_whole_trajectory(self, setup):
        cfg, _ = setup
        s = self._sched(cfg, n_blocks=4, bs=8)
        r1 = s.submit(self._prompt(8), 8)    # 1 block now + 1 reserved
        r2 = s.submit(self._prompt(8), 8)
        r3 = s.submit(self._prompt(8), 8)    # trajectory won't fit
        assert [r.req_id for r in s.admit()] == [r1.req_id, r2.req_id]
        assert s.pool.num_free == 2          # but both are spoken for
        assert [r.req_id for r in s.waiting] == [r3.req_id]
        # growth draws down the reservation, never the safety net
        # (requests grow only once prefill completed and they joined decode)
        r1.state = r2.state = "decoding"
        r1.n_cached = r2.n_cached = 8
        assert s.ensure_decode_blocks() == []
        assert s.pool.num_free == 0

    def test_preemption_safety_net_victim_is_youngest(self, setup):
        """Reservation makes preemption unreachable in normal operation;
        overrunning a reservation (future features: ignore-eos, parallel
        sampling) must still preempt the youngest request."""
        cfg, _ = setup
        s = self._sched(cfg, n_blocks=4, bs=8)
        r1 = s.submit(self._prompt(8), 8)
        r2 = s.submit(self._prompt(8), 8)
        s.admit()
        r1.state = r2.state = "decoding"     # prefilled + joined the batch
        r1.tokens.append(1), r2.tokens.append(1)
        r1.n_generated = r2.n_generated = 1
        r1.n_cached = r2.n_cached = 8
        s.ensure_decode_blocks()             # both grow into reservations
        r1.n_cached = r2.n_cached = 16       # overrun: pool is now dry
        preempted = s.ensure_decode_blocks()
        assert [r.req_id for r in preempted] == [r2.req_id]
        assert r2.state == "queued" and r2.tokens == [] and \
            r2.n_preemptions == 1
        assert s.tokens_discarded == 1       # r2's generated token
        assert s.waiting[0].req_id == r2.req_id     # head of the queue
        assert [r.req_id for r in s.running] == [r1.req_id]

    def test_submit_rejects_trajectory_larger_than_pool(self, setup):
        cfg, _ = setup
        s = self._sched(cfg, n_blocks=3, bs=8)
        with pytest.raises(ValueError):
            s.submit(self._prompt(8), 24)    # needs 4 > 3 blocks

    def test_submit_rejects_over_max_len(self, setup):
        cfg, _ = setup
        s = self._sched(cfg)
        with pytest.raises(ValueError):
            s.submit(self._prompt(60), 10)   # 70 > max_len 64


class TestPagedDecodeKernel:
    @pytest.mark.parametrize("B,Hq,Hkv,D,BS,nb", [
        (2, 4, 2, 16, 8, 4), (3, 8, 1, 32, 16, 3), (1, 2, 2, 64, 32, 2),
    ])
    def test_matches_contiguous_on_ragged_lengths(self, B, Hq, Hkv, D, BS,
                                                  nb):
        N = B * nb + 1
        q = jnp.asarray(_rng.normal(size=(B, Hq, D)), jnp.float32) / \
            np.sqrt(D)
        kp = jnp.asarray(_rng.normal(size=(N, Hkv, BS, D)), jnp.float32)
        vp = jnp.asarray(_rng.normal(size=(N, Hkv, BS, D)), jnp.float32)
        # disjoint per-sequence tables over blocks 1..N-1 (0 = garbage)
        perm = _rng.permutation(np.arange(1, N))[:B * nb]
        bt = jnp.asarray(perm.reshape(B, nb), jnp.int32)
        lens = jnp.asarray(_rng.integers(1, nb * BS + 1, (B,)), jnp.int32)

        got = flash_decode_paged(q, kp, vp, bt, lens, interpret=True)
        k = gather_kv(kp, bt)
        v = gather_kv(vp, bt)
        want = flash_decode(q, k, v, lens, block_k=BS, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)
        ref = paged_decode_ref(q, kp, vp, bt, lens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5)

    def test_zero_length_rows_are_finite_zeros(self):
        q = jnp.asarray(_rng.normal(size=(2, 2, 16)), jnp.float32)
        kp = jnp.asarray(_rng.normal(size=(5, 2, 8, 16)), jnp.float32)
        vp = jnp.asarray(_rng.normal(size=(5, 2, 8, 16)), jnp.float32)
        bt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
        out = flash_decode_paged(q, kp, vp, bt, jnp.zeros((2,), jnp.int32),
                                 interpret=True)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.zeros_like(np.asarray(out)))


def _random_pools(cfg, n_blocks, bs, quantized):
    """Pools of every layer filled with random rows, so that any write
    shows: bf16 values, or int8 codes with positive f32 scales."""
    shape = (cfg.n_layers, n_blocks, cfg.n_kv_heads, bs, cfg.head_dim_)
    if not quantized:
        return tuple(jnp.asarray(_rng.normal(size=shape), jnp.bfloat16)
                     for _ in "kv")
    codes = tuple(jnp.asarray(_rng.integers(-127, 128, shape), jnp.int8)
                  for _ in "kv")
    scales = tuple(jnp.asarray(_rng.uniform(0.01, 1.0, shape[:-1]),
                               jnp.float32) for _ in "kv")
    return codes + scales


def _changed_rows(old, new):
    """{(layer, block, head, offset)} of the pool rows that differ."""
    o, n = np.asarray(old), np.asarray(new)
    diff = o != n
    if diff.ndim == 5:
        diff = diff.any(axis=-1)
    return {tuple(int(i) for i in ix) for ix in np.argwhere(diff)}


class TestInPlacePoolUpdate:
    """The decode and chunk steps write each layer's new rows straight
    into the pool of every layer: after one step the pools differ from
    their input only at the written ``[layer, blk, :, off]`` rows and in
    the garbage block 0."""

    def _check(self, cfg, pools, out, rows):
        written = {(lay, b, h, o) for lay in range(cfg.n_layers)
                   for b, o in rows for h in range(cfg.n_kv_heads)}
        for old, new in zip(pools, out):
            assert new.shape == old.shape and new.dtype == old.dtype
            changed = _changed_rows(old, new)
            assert {r for r in changed if r[1] != 0} == written

    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["bf16", "int8"])
    def test_decode_step_writes_only_its_rows(self, setup, quantized):
        cfg, params = setup
        bs = 8
        pools = _random_pools(cfg, 9, bs, quantized)
        lengths = np.asarray([5, 17, 0], np.int32)   # row 2 is a zombie
        tables = np.asarray([[1, 2, 0], [3, 4, 5], [0, 0, 0]], np.int32)
        tokens = jnp.asarray(_rng.integers(1, cfg.vocab_size, 3), jnp.int32)
        sc = {"k_scale": pools[2], "v_scale": pools[3]} if quantized else {}
        lg, *out = paged_decode_step(params, tokens, pools[0], pools[1],
                                     jnp.asarray(tables),
                                     jnp.asarray(lengths), cfg, **sc)
        assert lg.shape[0] == 3 and len(out) == len(pools)
        rows = [(int(tables[b, ln // bs]), int(ln % bs))
                for b, ln in enumerate(lengths[:2])]
        self._check(cfg, pools, out, rows)

    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["bf16", "int8"])
    def test_chunk_step_writes_only_its_rows(self, setup, quantized):
        cfg, params = setup
        bs, C, sl, m = 8, 8, 6, 10     # 6 real rows at 10..15, 2 pad rows
        pools = _random_pools(cfg, 9, bs, quantized)
        table = np.asarray([6, 7], np.int32)          # exact cover of 16
        pos = m + np.arange(sl)
        blk = np.zeros(C, np.int32)                   # pad rows -> block 0
        off = np.zeros(C, np.int32)
        blk[:sl], off[:sl] = table[pos // bs], pos % bs
        tokens = np.zeros((1, C), np.int32)
        tokens[0, :sl] = _rng.integers(1, cfg.vocab_size, sl)
        sc = {"k_scale": pools[2], "v_scale": pools[3]} if quantized else {}
        lg, *out = paged_prefill_chunked(
            params, jnp.asarray(tokens), jnp.asarray(m, jnp.int32),
            jnp.asarray([sl - 1], jnp.int32), pools[0], pools[1],
            jnp.asarray(table[None]), jnp.asarray(blk), jnp.asarray(off),
            cfg, **sc)
        assert lg.shape[0] == 1 and len(out) == len(pools)
        self._check(cfg, pools, out, list(zip(blk[:sl].tolist(),
                                              off[:sl].tolist())))


class TestContinuousEngine:
    def test_greedy_matches_static_engine(self, setup):
        cfg, params = setup
        prompts = _rng.integers(1, cfg.vocab_size, (2, 20)).astype(np.int32)
        static = ServeEngine(cfg, params, max_len=26).generate(prompts, 6)

        eng = ContinuousEngine(cfg, params, block_size=8, num_blocks=32,
                               max_batch=4, max_len=32)
        handles = [eng.submit(p, 6) for p in prompts]
        res = eng.run()
        for h, want in zip(handles, static.tokens):
            assert res[h.req_id].tokens == want.tolist()

    def test_mixed_lengths_and_streaming(self, setup):
        cfg, params = setup
        eng = ContinuousEngine(cfg, params, block_size=8, num_blocks=32,
                               max_batch=4, max_len=48)
        lens = (5, 12, 24)
        handles = [eng.submit(
            _rng.integers(1, cfg.vocab_size, (n,)).astype(np.int32), 4)
            for n in lens]
        streamed = {}
        res = eng.run(on_token=lambda rid, toks:
                      streamed.setdefault(rid, []).extend(toks))
        for h in handles:
            assert len(res[h.req_id].tokens) == 4
            assert streamed[h.req_id] == res[h.req_id].tokens
        # every block is either free or retained by the prefix-cache tree
        cached = eng.prefix_cache.cached_blocks
        assert eng.pool.stats.blocks_in_use == cached
        assert eng.pool.num_free + cached == 32
        assert eng.metrics.tok_per_s > 0

    def test_scarce_pool_queues_and_recovers(self, setup):
        """Pool holds one trajectory at a time: requests serialize through
        the FIFO (no preemption thrash) and all finish."""
        cfg, params = setup
        eng = ContinuousEngine(cfg, params, block_size=8, num_blocks=4,
                               max_batch=4, max_len=48)
        handles = [eng.submit(
            _rng.integers(1, cfg.vocab_size, (16,)).astype(np.int32), 10)
            for _ in range(3)]
        res = eng.run()
        assert eng.metrics.preemptions == 0
        for h in handles:
            assert len(res[h.req_id].tokens) == 10
        # the tree keeps the last request's prompt blocks resident; the
        # rest of the scarce pool was evicted to admit each successor
        assert eng.pool.num_free + eng.prefix_cache.cached_blocks == 4
        assert eng.prefix_cache.stats.evictions > 0

    def test_mixed_temperature_batch(self, setup):
        """Greedy and sampled requests share one decode batch (the engine
        falls back to host-side sampling for the sampled rows)."""
        cfg, params = setup
        eng = ContinuousEngine(cfg, params, block_size=8, num_blocks=32,
                               max_batch=4, max_len=32, seed=7)
        h_greedy = eng.submit(
            _rng.integers(1, cfg.vocab_size, (12,)).astype(np.int32), 5)
        h_sampled = eng.submit(
            _rng.integers(1, cfg.vocab_size, (12,)).astype(np.int32), 5,
            temperature=1.0)
        res = eng.run()
        for h in (h_greedy, h_sampled):
            toks = res[h.req_id].tokens
            assert len(toks) == 5
            assert all(0 <= t < cfg.vocab_size for t in toks)

    def test_unsupported_family_rejected(self, setup):
        cfg = reduce_config(get_config("rwkv6-7b"))
        with pytest.raises(ValueError):
            ContinuousEngine(cfg, params=None)
