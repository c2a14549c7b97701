"""Paged chunked prefill: kernel vs oracle parity, equivalence with the
dense one-shot suffix path, engine-level greedy equality across chunk
boundaries, decode/prefill interleaving, and multi-turn generated-token
reuse."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_prefill_paged import (flash_prefill_paged,
                                               flash_prefill_paged_op,
                                               paged_prefill_ref,
                                               paged_prefill_split_ref)
from repro.models.attention import quantize_kv
from repro.models.registry import get_config, model_fns, reduce_config
from repro.serve import ContinuousEngine
from repro.serve.kv_pool import PagedKVCache
from repro.serve.paged_step import (paged_prefill, paged_prefill_chunked,
                                    paged_prefill_suffix, scatter_prefill,
                                    scatter_prefill_offset)

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

    def test_ref_matches_dense_suffix_attention(self):
        """The single-table positional-causal oracle computes the same
        attention as PR-2's gather-and-concat ``_suffix_attention`` when
        the suffix KV is pool-resident."""
        from repro.serve.paged_step import _suffix_attention
        B, Hq, Hkv, D, BS, Sq, pos0 = 1, 4, 2, 16, 8, 19, 21
        W = -(-(pos0 + Sq) // BS)
        kp, vp, bt = _random_paged_kv(B, Hkv, D, BS, W)
        q = jnp.asarray(_rng.normal(size=(B, Hq, Sq, D)),
                        jnp.float32) / np.sqrt(D)
        got = paged_prefill_ref(q, kp, vp, bt,
                                jnp.asarray([pos0], jnp.int32))
        # dense path: gather prefix rows [0, pos0) and suffix rows
        # [pos0, pos0+Sq) out of the same pool, then concat + mask
        from repro.kernels.flash_decode_paged.ref import gather_kv
        kv_all_k = gather_kv(kp, bt)
        kv_all_v = gather_kv(vp, bt)
        W_pre = -(-pos0 // BS)          # prefix table incl. partial tail
        k_pre = gather_kv(kp, bt[:, :W_pre])
        v_pre = gather_kv(vp, bt[:, :W_pre])
        k_suf = kv_all_k[:, :, pos0:pos0 + Sq]
        v_suf = kv_all_v[:, :, pos0:pos0 + Sq]
        pre_valid = jnp.arange(W_pre * BS)[None, :] < pos0
        q_pos = pos0 + jnp.arange(Sq)[None, :]
        want = _suffix_attention(q, k_pre, v_pre, k_suf, v_suf, pre_valid,
                                 q_pos, intmax=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

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


class TestChunkedPrefillStep:
    """Model-level: chunked == one-shot over identical pool state."""

    def _resident_prefix(self, cfg, params, prompt, m0, pool, table, bs):
        toks = jnp.asarray(prompt[None, :m0], jnp.int32)
        _, ks, vs = paged_prefill(params, toks,
                                  jnp.asarray([m0 - 1], jnp.int32), cfg)
        pool.k, pool.v = scatter_prefill(
            pool.k, pool.v, ks, vs, jnp.asarray(table[:m0 // bs], jnp.int32))

    @pytest.mark.parametrize("m0,chunk", [(16, 16), (16, 24), (32, 8)])
    def test_chunked_equals_one_shot_suffix(self, setup, m0, chunk):
        """Walking the suffix in chunks (incl. chunk sizes that straddle
        block boundaries) must reproduce the one-shot dense suffix
        prefill: same final logits, same pool contents."""
        cfg, params = setup
        bs = 8
        S = 72                           # suffix of 56 = 7 blocks
        prompt = _rng.integers(1, cfg.vocab_size, (S,)).astype(np.int32)
        pools = {}
        for mode in ("dense", "chunked"):
            pool = PagedKVCache(cfg, num_blocks=S // bs, block_size=bs)
            table = np.asarray(pool.alloc(0, S // bs), np.int32)
            self._resident_prefix(cfg, params, prompt, m0, pool, table, bs)
            sl = S - m0
            pos = m0 + np.arange(sl)
            blk = jnp.asarray(table[pos // bs], jnp.int32)
            off = jnp.asarray(pos % bs, jnp.int32)
            if mode == "dense":
                lg, ks, vs = paged_prefill_suffix(
                    params, jnp.asarray(prompt[None, m0:], jnp.int32),
                    jnp.asarray(m0, jnp.int32),
                    jnp.asarray([sl - 1], jnp.int32), pool.k, pool.v,
                    jnp.asarray(table[None, :m0 // bs], jnp.int32),
                    jnp.asarray([m0], jnp.int32), cfg)
                pool.k, pool.v = scatter_prefill_offset(
                    pool.k, pool.v, ks, vs, blk, off)
            else:
                m = m0
                while m < S:
                    c = min(chunk, S - m)
                    cover = -(-(m + c) // bs)
                    lg, pool.k, pool.v = paged_prefill_chunked(
                        params, jnp.asarray(prompt[None, m:m + c],
                                            jnp.int32),
                        jnp.asarray(m, jnp.int32),
                        jnp.asarray([c - 1], jnp.int32), pool.k, pool.v,
                        jnp.asarray(table[None, :cover], jnp.int32),
                        blk[m - m0:m - m0 + c], off[m - m0:m - m0 + c],
                        cfg)
                    m += c
            pools[mode] = (np.asarray(lg), np.asarray(pool.k),
                           np.asarray(pool.v))
        lg_d, k_d, v_d = pools["dense"]
        lg_c, k_c, v_c = pools["chunked"]
        np.testing.assert_allclose(lg_c, lg_d, atol=2e-4)
        assert np.argmax(lg_c) == np.argmax(lg_d)
        np.testing.assert_allclose(k_c, k_d, atol=1e-5)
        np.testing.assert_allclose(v_c, v_d, atol=1e-5)


class TestChunkedEngine:
    @pytest.mark.parametrize("chunk", [8, 24])
    def test_greedy_identical_to_one_shot(self, setup, chunk):
        """Odd prompt lengths (ragged final chunks, mid-block ends) decode
        identically chunked vs one-shot."""
        cfg, params = setup
        lens = (5, 20, 37, 64)
        prompts = [_rng.integers(1, cfg.vocab_size, (n,)).astype(np.int32)
                   for n in lens]
        outs = {}
        for c in (0, chunk):
            eng = ContinuousEngine(cfg, params, block_size=8, num_blocks=64,
                                   max_batch=4, max_len=96, prefill_chunk=c)
            hs = [eng.submit(p, 6) for p in prompts]
            res = eng.run()
            outs[c] = [res[h.req_id].tokens for h in hs]
            for toks in outs[c]:
                assert len(toks) == 6
        assert outs[0] == outs[chunk]

    def test_prefix_cache_mid_block_offsets(self, setup):
        """Shared prefix of non-block-multiple length: chunked prefill
        starts mid-block after the COW tail splice and must agree with the
        one-shot path."""
        cfg, params = setup
        shared = _rng.integers(1, cfg.vocab_size, (21,)).astype(np.int32)
        prompts = [np.concatenate(
            [shared, _rng.integers(1, cfg.vocab_size, (n,))]).astype(
                np.int32) for n in (13, 30, 7)]
        outs = {}
        for c in (0, 16):
            eng = ContinuousEngine(cfg, params, block_size=8, num_blocks=64,
                                   max_batch=4, max_len=96, prefill_chunk=c)
            hs = [eng.submit(p, 5) for p in prompts]
            res = eng.run()
            outs[c] = [res[h.req_id].tokens for h in hs]
        assert outs[0] == outs[16]

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


@pytest.mark.slow
class TestBenchSmoke:
    def test_prefill_paged_bench_smoke(self):
        """The benchmark's CI mode: asserts chunked == dense greedy
        outputs (op-level argmax and engine-level tokens) on a tiny
        workload; speed is reported, not gated."""
        import pathlib
        import sys
        root = pathlib.Path(__file__).resolve().parent.parent
        sys.path.insert(0, str(root / "benchmarks"))
        try:
            import prefill_paged_bench
            ratio = prefill_paged_bench.main(["--smoke"])
        finally:
            sys.path.pop(0)
        assert ratio > 0
