"""Paged chunked prefill, the one prefill path: kernel vs oracle parity,
chunked walks against one whole-prompt chunk and against the contiguous
``models/lm.py`` forward, decode as a one-token chunk, engine-level greedy
equality across chunk sizes and with the prefix cache on or off,
decode/prefill interleaving, and multi-turn generated-token reuse."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_prefill_paged import (flash_prefill_paged,
                                               flash_prefill_paged_op,
                                               paged_prefill_ref,
                                               paged_prefill_split_ref)
from repro.models.attention import quantize_kv
from repro.models.lm import lm_prefill
from repro.models.registry import get_config, model_fns, reduce_config
from repro.serve import ContinuousEngine
from repro.serve.engine import PREFILL_CHUNK
from repro.serve.kv_pool import PagedKVCache
from repro.serve.paged_step import (paged_decode_step, paged_prefill_chunked,
                                    scale_kwargs)

_rng = np.random.default_rng(23)


@pytest.fixture(scope="module")
def setup():
    cfg = reduce_config(get_config("qwen3-4b"))
    fns = model_fns(cfg)
    params = fns.init(jax.random.PRNGKey(0))
    return cfg, params


def _random_paged_kv(B, Hkv, D, BS, W, *, shuffle=True):
    """Pool + per-sequence disjoint tables over blocks 1.. (0 = garbage)."""
    N = B * W + 1
    kp = jnp.asarray(_rng.normal(size=(N, Hkv, BS, D)), jnp.float32)
    vp = jnp.asarray(_rng.normal(size=(N, Hkv, BS, D)), jnp.float32)
    ids = np.arange(1, N)
    if shuffle:
        ids = _rng.permutation(ids)
    bt = jnp.asarray(ids[:B * W].reshape(B, W), jnp.int32)
    return kp, vp, bt


class TestFlashPrefillPagedKernel:
    @pytest.mark.parametrize("B,Hq,Hkv,D,BS,Sq,pos0s,bq", [
        (2, 4, 2, 16, 8, 7, (0, 5), 8),       # odd suffix, mid-block start
        (2, 8, 2, 32, 16, 33, (13, 40), 16),  # odd suffix, multi-tile q
        (1, 2, 2, 64, 8, 16, (9,), 4),        # start mid-block, tiny tiles
        (3, 4, 4, 16, 8, 24, (0, 17, 3), 128),  # block_q > Sq (clamped)
    ])
    def test_matches_ref_on_ragged_geometry(self, B, Hq, Hkv, D, BS, Sq,
                                            pos0s, bq):
        W = -(-(max(pos0s) + Sq) // BS)
        kp, vp, bt = _random_paged_kv(B, Hkv, D, BS, W)
        q = jnp.asarray(_rng.normal(size=(B, Hq, Sq, D)),
                        jnp.float32) / np.sqrt(D)
        pos0 = jnp.asarray(pos0s, jnp.int32)
        got = flash_prefill_paged(q, kp, vp, bt, pos0, interpret=True,
                                  block_q=bq)
        want = paged_prefill_ref(q, kp, vp, bt, pos0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    @pytest.mark.parametrize("T", [1, 2, 4])
    def test_multi_block_tiles_match_ref(self, T):
        """kv_tile_blocks is layout-only: T pool blocks per kv grid step
        (table padded to a tile multiple with garbage block 0, pad tiles
        skipped above the diagonal) computes the identical attention on
        ragged geometry — odd suffix, mid-block start, non-tile-multiple
        table width."""
        B, Hq, Hkv, D, BS, Sq, bq = 2, 8, 2, 16, 8, 19, 8
        pos0s = (11, 26)
        W = -(-(max(pos0s) + Sq) // BS)
        assert W % T or T == 1 or W // T > 1   # keep the ragged case real
        kp, vp, bt = _random_paged_kv(B, Hkv, D, BS, W)
        q = jnp.asarray(_rng.normal(size=(B, Hq, Sq, D)),
                        jnp.float32) / np.sqrt(D)
        pos0 = jnp.asarray(pos0s, jnp.int32)
        got = flash_prefill_paged(q, kp, vp, bt, pos0, interpret=True,
                                  block_q=bq, kv_tile_blocks=T)
        want = paged_prefill_ref(q, kp, vp, bt, pos0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    @pytest.mark.parametrize("T", [1, 2])
    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["bf16", "int8"])
    def test_layer_index_reads_that_layer(self, T, quantized):
        """A pool that keeps its layer axis, read at ``layer=1``, gives bit
        for bit what the one-layer pool ``pool[1]`` gives: under the
        interpreter and through both CPU references."""
        B, Hq, Hkv, D, BS, Sq, bq = 2, 8, 2, 16, 8, 19, 8
        pos0s = (11, 26)
        W = -(-(max(pos0s) + Sq) // BS)
        kp, vp, bt = _random_paged_kv(B, Hkv, D, BS, W)
        kp = jnp.stack([kp, kp + 1.0, kp - 1.0])
        vp = jnp.stack([vp, vp * 0.5, vp * 2.0])
        sc = {}
        if quantized:
            kp, ksc = quantize_kv(kp)
            vp, vsc = quantize_kv(vp)
            sc = {"k_scale": ksc, "v_scale": vsc}
        else:
            kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
        sc1 = {k: v[1] for k, v in sc.items()}
        q = jnp.asarray(_rng.normal(size=(B, Hq, Sq, D)),
                        jnp.float32) / np.sqrt(D)
        pos0 = jnp.asarray(pos0s, jnp.int32)
        tail = 2 * (-(-Sq // BS)) + 1
        pairs = [
            (flash_prefill_paged(q, kp, vp, bt, pos0, layer=1, block_q=bq,
                                 kv_tile_blocks=T, interpret=True, **sc),
             flash_prefill_paged(q, kp[1], vp[1], bt, pos0, block_q=bq,
                                 kv_tile_blocks=T, interpret=True, **sc1)),
            (paged_prefill_ref(q, kp, vp, bt, pos0, layer=1, **sc),
             paged_prefill_ref(q, kp[1], vp[1], bt, pos0, **sc1)),
            (paged_prefill_split_ref(q, kp, vp, bt, pos0, layer=1,
                                     tail_blocks=tail, **sc),
             paged_prefill_split_ref(q, kp[1], vp[1], bt, pos0,
                                     tail_blocks=tail, **sc1))]
        for got, want in pairs:
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        other = paged_prefill_ref(q, kp[0], vp[0], bt, pos0,
                                  **{k: v[0] for k, v in sc.items()})
        assert not np.array_equal(np.asarray(pairs[0][0]),
                                  np.asarray(other))

    @pytest.mark.parametrize("Sq,pos0,pad_to_cq", [
        (16, 24, False),    # exact cover, mid-block-free
        (16, 21, False),    # exact cover, mid-block pos0
        (16, 37, True),     # cover quantized to chunk blocks + pad
        (8, 3, False),      # W <= tail_blocks: whole table masked
    ])
    def test_split_ref_matches_oracle(self, Sq, pos0, pad_to_cq):
        """The serve-path split oracle (mask-free prefix bulk + masked
        static tail) is the same attention under its table contract —
        exact cover, or cover rounded to chunk-block multiples with
        garbage-block padding."""
        B, Hq, Hkv, D, BS = 1, 4, 2, 16, 8
        cq = -(-Sq // BS)
        cover = -(-(pos0 + Sq) // BS)
        W = (-(-cover // cq) * cq) if pad_to_cq else cover
        kp, vp, bt_full = _random_paged_kv(B, Hkv, D, BS, cover)
        bt = np.zeros((B, W), np.int32)          # pad entries -> block 0
        bt[:, :cover] = np.asarray(bt_full)
        bt = jnp.asarray(bt)
        q = jnp.asarray(_rng.normal(size=(B, Hq, Sq, D)),
                        jnp.float32) / np.sqrt(D)
        p0 = jnp.asarray([pos0], jnp.int32)
        want = paged_prefill_ref(q, kp, vp, bt, p0)
        got = paged_prefill_split_ref(q, kp, vp, bt, p0,
                                      tail_blocks=2 * cq + 1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    def test_cpu_dispatch_falls_back_to_ref(self):
        """Interpret-mode fallback assertion: off-TPU, the op must run (no
        compiled-Pallas requirement) and agree with the pure-JAX oracle in
        both its fallback modes."""
        B, Hq, Hkv, D, BS, Sq, pos0 = 2, 4, 2, 16, 8, 11, 6
        W = -(-(pos0 + Sq) // BS)
        kp, vp, bt = _random_paged_kv(B, Hkv, D, BS, W)
        q = jnp.asarray(_rng.normal(size=(B, Hq, Sq, D)),
                        jnp.float32) / np.sqrt(D)
        pos0 = jnp.asarray([pos0, 3], jnp.int32)
        want = paged_prefill_ref(q, kp, vp, bt, pos0)
        default = flash_prefill_paged_op(q, kp, vp, bt, pos0)
        interp = flash_prefill_paged_op(q, kp, vp, bt, pos0, interpret=True)
        if jax.default_backend() != "tpu":
            # default dispatch IS the oracle off-TPU — bitwise identical
            np.testing.assert_array_equal(np.asarray(default),
                                          np.asarray(want))
        np.testing.assert_allclose(np.asarray(interp), np.asarray(want),
                                   atol=1e-5)

    @pytest.mark.tpu
    def test_compiled_matches_interpret(self):
        """Compiled-Pallas parity — only meaningful (and only runnable) on
        a real TPU backend; conftest skips it cleanly elsewhere."""
        B, Hq, Hkv, D, BS, Sq, pos0 = 1, 4, 2, 128, 16, 32, 24
        W = -(-(pos0 + Sq) // BS)
        kp, vp, bt = _random_paged_kv(B, Hkv, D, BS, W)
        q = jnp.asarray(_rng.normal(size=(B, Hq, Sq, D)),
                        jnp.float32) / np.sqrt(D)
        pos0 = jnp.asarray([pos0], jnp.int32)
        got = flash_prefill_paged(q, kp, vp, bt, pos0)
        want = flash_prefill_paged(q, kp, vp, bt, pos0, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)


def _chunk(cfg, params, prompt, m, c, pools, table, bs):
    """Prefill ``prompt[m:m + c]`` as one chunk at ``pos0 = m`` through
    the exact-cover table; returns (logits, pools)."""
    cover = -(-(m + c) // bs)
    pos = m + np.arange(c)
    lg, *pools = paged_prefill_chunked(
        params, jnp.asarray(prompt[None, m:m + c], jnp.int32),
        jnp.asarray(m, jnp.int32), jnp.asarray([c - 1], jnp.int32),
        pools[0], pools[1], jnp.asarray(table[None, :cover], jnp.int32),
        jnp.asarray(table[pos // bs], jnp.int32),
        jnp.asarray(pos % bs, jnp.int32), cfg, **scale_kwargs(pools))
    return lg, tuple(pools)


def _fresh_pool(cfg, nb, bs, kv_dtype="auto"):
    pool = PagedKVCache(cfg, num_blocks=nb, block_size=bs, kv_dtype=kv_dtype)
    table = np.asarray(pool.alloc(0, nb), np.int32)
    pools = (pool.k, pool.v) + ((pool.k_scale, pool.v_scale)
                                if pool.quantized else ())
    return pools, table


class TestChunkedPrefillStep:
    """Model-level: chunk walks over identical pool state."""

    def _resident_prefix(self, cfg, params, prompt, m0, pools, table, bs):
        """The first ``m0`` prompt tokens made pool-resident by one chunk
        from position 0."""
        return _chunk(cfg, params, prompt, 0, m0, pools, table, bs)[1]

    @pytest.mark.parametrize("m0,chunk", [(16, 16), (16, 24), (32, 8)])
    def test_chunked_equals_one_shot_suffix(self, setup, m0, chunk):
        """Walking the suffix in chunks over a resident prefix (incl.
        chunk sizes that straddle block boundaries) must reproduce one
        chunk of the whole prompt from position 0: same final logits,
        same pool contents."""
        cfg, params = setup
        bs = 8
        S = 72                           # suffix of 56 = 7 blocks
        prompt = _rng.integers(1, cfg.vocab_size, (S,)).astype(np.int32)
        out = {}
        for mode in ("whole", "chunked"):
            pools, table = _fresh_pool(cfg, S // bs, bs)
            if mode == "whole":
                lg, pools = _chunk(cfg, params, prompt, 0, S, pools, table,
                                   bs)
            else:
                pools = self._resident_prefix(cfg, params, prompt, m0,
                                              pools, table, bs)
                m = m0
                while m < S:
                    c = min(chunk, S - m)
                    lg, pools = _chunk(cfg, params, prompt, m, c, pools,
                                       table, bs)
                    m += c
            out[mode] = (np.asarray(lg), np.asarray(pools[0]),
                         np.asarray(pools[1]))
        lg_w, k_w, v_w = out["whole"]
        lg_c, k_c, v_c = out["chunked"]
        np.testing.assert_allclose(lg_c, lg_w, atol=2e-4)
        assert np.argmax(lg_c) == np.argmax(lg_w)
        np.testing.assert_allclose(k_c, k_w, atol=1e-5)
        np.testing.assert_allclose(v_c, v_w, atol=1e-5)

    @pytest.mark.parametrize("n", [13, 16])      # mid-block; block start
    @pytest.mark.parametrize("kv_dtype", ["auto", "int8"])
    def test_decode_step_is_a_one_token_chunk(self, setup, n, kv_dtype):
        """Both serving programs run one layer: a decode step of token t
        at length n gives the logits and pool rows of a one-token chunk
        of t at ``pos0 = n``."""
        cfg, params = setup
        bs = 8
        prompt = _rng.integers(1, cfg.vocab_size, (n + 1,)).astype(np.int32)
        pools, table = _fresh_pool(cfg, 4, bs, kv_dtype)
        pools = self._resident_prefix(cfg, params, prompt, n, pools, table,
                                      bs)
        lg_c, after_c = _chunk(cfg, params, prompt, n, 1, pools, table, bs)
        lg_d, *after_d = paged_decode_step(
            params, jnp.asarray(prompt[n:n + 1], jnp.int32), pools[0],
            pools[1], jnp.asarray(table[None], jnp.int32),
            jnp.asarray([n], jnp.int32), cfg, **scale_kwargs(pools))
        np.testing.assert_allclose(np.asarray(lg_d), np.asarray(lg_c),
                                   atol=1e-5)
        for a, b in zip(after_d, after_c):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), atol=1e-5)
        blk, off = table[n // bs], n % bs
        assert np.abs(np.asarray(after_d[0][:, blk, :, off],
                                 np.float32)).max() > 0     # row written

    def test_whole_prompt_chunk_matches_contiguous_forward(self, setup):
        """One chunk of a whole prompt from position 0 gives the last-token
        logits of ``models/lm.py``'s contiguous prefill."""
        cfg, params = setup
        bs, S = 8, 37
        prompt = _rng.integers(1, cfg.vocab_size, (S,)).astype(np.int32)
        pools, table = _fresh_pool(cfg, -(-S // bs), bs)
        lg, _ = _chunk(cfg, params, prompt, 0, S, pools, table, bs)
        want, _ = lm_prefill(params, jnp.asarray(prompt[None]), cfg, S)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(want),
                                   atol=1e-4)
        assert np.argmax(lg) == np.argmax(want)


class TestChunkedEngine:
    @pytest.mark.parametrize("chunk", [8, 24])
    def test_greedy_identical_to_one_shot(self, setup, chunk):
        """Odd prompt lengths (ragged final chunks, mid-block ends) decode
        identically in small chunks and in one chunk covering the longest
        prompt."""
        cfg, params = setup
        lens = (5, 20, 37, 64)
        prompts = [_rng.integers(1, cfg.vocab_size, (n,)).astype(np.int32)
                   for n in lens]
        outs = {}
        for c in (max(lens), chunk):
            eng = ContinuousEngine(cfg, params, block_size=8, num_blocks=64,
                                   max_batch=4, max_len=96, prefill_chunk=c)
            hs = [eng.submit(p, 6) for p in prompts]
            res = eng.run()
            outs[c] = [res[h.req_id].tokens for h in hs]
            for toks in outs[c]:
                assert len(toks) == 6
        assert outs[max(lens)] == outs[chunk]

    def test_prefix_cache_mid_block_offsets(self, setup):
        """Shared prefix of non-block-multiple length: chunked prefill
        starts mid-block after the COW tail splice and must agree with an
        engine that computes every prompt from position 0."""
        cfg, params = setup
        shared = _rng.integers(1, cfg.vocab_size, (21,)).astype(np.int32)
        prompts = [np.concatenate(
            [shared, _rng.integers(1, cfg.vocab_size, (n,))]).astype(
                np.int32) for n in (13, 30, 7)]
        outs = {}
        for cache in (True, False):
            eng = ContinuousEngine(cfg, params, block_size=8, num_blocks=64,
                                   max_batch=4, max_len=96, prefill_chunk=16,
                                   prefix_cache=cache,
                                   max_admit_per_step=1)
            hs = [eng.submit(p, 5) for p in prompts]
            res = eng.run()
            outs[cache] = [res[h.req_id].tokens for h in hs]
            if cache:
                assert eng.metrics.cow_copies >= 1     # mid-block resume
                assert eng.metrics.prefix_hit_tokens > 0
        assert outs[True] == outs[False]

    def test_long_prompt_does_not_stall_decode(self, setup):
        """Interleaving: a short request already decoding keeps producing
        tokens on the very steps a long prompt spends prefilling."""
        cfg, params = setup
        eng = ContinuousEngine(cfg, params, block_size=8, num_blocks=64,
                               max_batch=4, max_len=128, prefill_chunk=8,
                               max_admit_per_step=1)
        short = eng.submit(
            _rng.integers(1, cfg.vocab_size, (8,)).astype(np.int32), 16)
        eng.step()                       # short joins the decode batch
        assert short.state == "decoding"
        long = eng.submit(
            _rng.integers(1, cfg.vocab_size, (80,)).astype(np.int32), 8)
        decoded_during_prefill = 0
        for _ in range(40):              # bounded: 1 admit + 10 chunks
            eng.step()
            if long.state == "prefill":
                decoded_during_prefill += 1
                assert short.n_generated > 0
            if long.state not in ("queued", "prefill"):
                break
        assert long.state == "decoding"
        n_before_join = short.n_generated
        # 80 tokens at chunk 8 = 10 chunks; decode advanced alongside
        assert decoded_during_prefill >= 9
        assert n_before_join >= 9
        eng.run()

    def test_multi_turn_generated_tokens_reused(self, setup):
        """Finish publishes drained decode tokens into the radix tree: a
        follow-up turn extending [prompt ‖ reply] must hit the cache for
        the whole conversation so far, and still decode exactly like a
        cold engine."""
        cfg, params = setup
        eng = ContinuousEngine(cfg, params, block_size=8, num_blocks=64,
                               max_batch=4, max_len=96, prefill_chunk=16)
        pA = _rng.integers(1, cfg.vocab_size, (19,)).astype(np.int32)
        h1 = eng.submit(pA, 12)
        r1 = eng.run()
        reply = r1[h1.req_id].tokens
        follow = np.concatenate(
            [pA, np.asarray(reply, np.int32),
             _rng.integers(1, cfg.vocab_size, (7,))]).astype(np.int32)
        hit0 = eng.metrics.prefix_hit_tokens
        h2 = eng.submit(follow, 4)
        r2 = eng.run()
        hit = eng.metrics.prefix_hit_tokens - hit0
        # prompt (19) + cached generated KV (11 = max_new - 1) = 30
        # resident tokens; ≥ 3 full blocks of those must be reused
        assert hit >= 24, hit
        cold = ContinuousEngine(cfg, params, block_size=8, num_blocks=64,
                                max_batch=4, max_len=96, prefix_cache=False)
        h3 = cold.submit(follow, 4)
        r3 = cold.run()
        assert r2[h2.req_id].tokens == r3[h3.req_id].tokens

    def test_prefill_chunk_default_and_bounds(self, setup):
        """The chunk defaults to 256 tokens, cut to ``max_len`` and rounded
        up to whole blocks; a chunk of no tokens is refused."""
        cfg, params = setup

        def chunk(**kw):
            return ContinuousEngine(cfg, params, block_size=8,
                                    num_blocks=8, **kw).prefill_chunk

        assert PREFILL_CHUNK == 256
        assert chunk(max_len=512) == 256
        assert chunk(max_len=100) == 104
        assert chunk(max_len=512, prefill_chunk=13) == 16
        for bad in (0, -8):
            with pytest.raises(ValueError):
                chunk(max_len=512, prefill_chunk=bad)

    def test_warmup_covers_chunked_path(self, setup):
        cfg, params = setup
        eng = ContinuousEngine(cfg, params, block_size=8, num_blocks=32,
                               max_batch=2, max_len=48, prefill_chunk=16)
        eng.warmup()
        assert eng.metrics.steps == 0    # metrics reset after warmup
        h = eng.submit(
            _rng.integers(1, cfg.vocab_size, (20,)).astype(np.int32), 4)
        res = eng.run()
        assert len(res[h.req_id].tokens) == 4
