"""GQA-grouped / multi-block-tiled / split-K paged decode: Softermax-merge
operator properties (hypothesis), kernel-vs-ref parity sweeps across tile
sizes and split factors (bf16 + int8), legacy-kernel equivalence, the
shared table-width bucketing policy, and engine greedy equality across
grid settings."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.numerics import NEG_INF
from repro.core.softermax import softermax_finalize, softermax_merge
from repro.kernels.flash_decode_paged import (flash_decode_paged,
                                              flash_decode_paged_single,
                                              paged_decode_ref,
                                              paged_decode_split_ref,
                                              split_layout)
from repro.kernels.flash_decode_paged.ref import (decode_tile_blocks,
                                                  live_tile_range)
from repro.models.attention import quantize_kv
from repro.models.registry import get_config, model_fns, reduce_config
from repro.serve import ContinuousEngine
from repro.serve.paged_step import table_width_bucket

_rng = np.random.default_rng(11)


# ---------------------------------------------------------------------------
# softermax_merge: operator properties
# ---------------------------------------------------------------------------


def _state_of(scores: np.ndarray, intmax: bool, col_scale=None):
    """Closed-form partial state of one score segment (rows, cols) against
    unit values — the (m, d, acc) a kernel lane leaves behind. Empty
    segments (cols == 0) give the merge identity. ``col_scale`` mimics the
    int8 dequant fused into the score row."""
    if scores.shape[-1] == 0:
        rows = scores.shape[0]
        return (np.full((rows, 1), NEG_INF, np.float32),
                np.zeros((rows, 1), np.float32),
                np.zeros((rows, 1), np.float32))
    s = scores.astype(np.float32)
    if col_scale is not None:
        s = s * col_scale[None, :]
    m = np.max(s, axis=-1, keepdims=True)
    if intmax:
        m = np.ceil(m)
    p = np.exp2(s - m)
    d = np.sum(p, axis=-1, keepdims=True)
    acc = np.sum(p, axis=-1, keepdims=True)  # values == 1: acc mirrors d
    return m.astype(np.float32), d.astype(np.float32), acc.astype(np.float32)


def _merge_pair(a, b):
    m = jnp.stack([a[0], b[0]], 0)
    d = jnp.stack([a[1], b[1]], 0)
    acc = jnp.stack([a[2], b[2]], 0)
    out = softermax_merge(m, d, acc, axis=0)
    return tuple(np.asarray(x) for x in out)


def _rand_segments(rng, n_seg, max_rows=3, max_cols=9, allow_empty=True):
    rows = int(rng.integers(1, max_rows + 1))
    lo = 0 if allow_empty else 1
    return [rng.uniform(-30.0, 30.0,
                        (rows, int(rng.integers(lo, max_cols + 1)))
                        ).astype(np.float32) for _ in range(n_seg)]


def _check_merge_equals_whole(segs, intmax, col_scales=None):
    """Splitting a score row into segments, reducing each, and merging
    must reproduce the unsplit reduction — the exact property that makes
    split-K legal for Softermax."""
    cs = col_scales or [None] * len(segs)
    states = [_state_of(s, intmax, col_scale=c) for s, c in zip(segs, cs)]
    m = jnp.stack([s[0] for s in states], 0)
    d = jnp.stack([s[1] for s in states], 0)
    acc = jnp.stack([s[2] for s in states], 0)
    _, d2, acc2 = softermax_merge(m, d, acc, axis=0)
    whole = _state_of(
        np.concatenate(segs, axis=-1), intmax,
        col_scale=None if col_scales is None else np.concatenate(cs))
    np.testing.assert_allclose(np.asarray(d2), whole[1], rtol=1e-5,
                               atol=1e-30)
    np.testing.assert_allclose(np.asarray(acc2), whole[2], rtol=1e-5,
                               atol=1e-30)


def _check_commutative(segs, intmax):
    """Pairwise merge is exactly commutative (max and two-term sums are
    order-symmetric in IEEE arithmetic)."""
    a, b = (_state_of(s, intmax) for s in segs[:2])
    ab, ba = _merge_pair(a, b), _merge_pair(b, a)
    for x, y in zip(ab, ba):
        np.testing.assert_array_equal(x, y)


def _check_associative(segs, intmax):
    """(a ⊕ b) ⊕ c == a ⊕ (b ⊕ c) — exactly for the rescales (integer
    exponent adds under IntMax), up to fp addition order for the sums."""
    a, b, c = (_state_of(s, intmax) for s in segs[:3])
    left = _merge_pair(_merge_pair(a, b), c)
    right = _merge_pair(a, _merge_pair(b, c))
    for x, y in zip(left, right):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-30)


def _check_permutation_invariant(segs, perm, intmax):
    """n-ary merge must not care which split lane produced which
    partition."""
    states = [_state_of(s, intmax) for s in segs]

    def nary(order):
        m = jnp.stack([states[i][0] for i in order], 0)
        d = jnp.stack([states[i][1] for i in order], 0)
        acc = jnp.stack([states[i][2] for i in order], 0)
        return softermax_merge(m, d, acc, axis=0)

    base, shuf = nary(range(len(segs))), nary(perm)
    for x, y in zip(base[1:], shuf[1:]):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-30)


def _check_identity_exact(seg, intmax):
    """Merging with (NEG_INF, 0, 0) — an empty partition — changes
    nothing, bit for bit."""
    a = _state_of(seg, intmax)
    out = _merge_pair(a, _state_of(seg[:, :0], intmax))
    np.testing.assert_array_equal(out[1], a[1])
    np.testing.assert_array_equal(out[2], a[2])
    np.testing.assert_array_equal(
        np.asarray(softermax_finalize(jnp.asarray(out[2]),
                                      jnp.asarray(out[1]))),
        np.asarray(softermax_finalize(jnp.asarray(a[2]),
                                      jnp.asarray(a[1]))))


class TestSoftermaxMerge:
    """Seeded sweeps of the operator laws (no-dependency fallback for the
    hypothesis test below, same checkers)."""

    @pytest.mark.parametrize("intmax", [True, False])
    def test_merge_of_partials_equals_whole(self, intmax):
        rng = np.random.default_rng(2)
        for _ in range(25):
            _check_merge_equals_whole(_rand_segments(rng, 3), intmax)

    @pytest.mark.parametrize("intmax", [True, False])
    def test_commutative_and_associative(self, intmax):
        rng = np.random.default_rng(3)
        for _ in range(25):
            segs = _rand_segments(rng, 3)
            _check_commutative(segs, intmax)
            _check_associative(segs, intmax)

    @pytest.mark.parametrize("intmax", [True, False])
    def test_permutation_invariant(self, intmax):
        rng = np.random.default_rng(4)
        for _ in range(25):
            segs = _rand_segments(rng, 4)
            _check_permutation_invariant(
                segs, list(rng.permutation(len(segs))), intmax)

    @pytest.mark.parametrize("intmax", [True, False])
    def test_identity_state_is_exact(self, intmax):
        rng = np.random.default_rng(5)
        for _ in range(25):
            _check_identity_exact(_rand_segments(rng, 1,
                                                 allow_empty=False)[0],
                                  intmax)

    def test_int8_scaled_path(self):
        """States built from scale-dequantized score rows (the fused int8
        path: S *= k_scale post-dot) merge identically to the whole-row
        reduction — the merge never sees the scales, only states."""
        rng = np.random.default_rng(7)
        for _ in range(25):
            segs = _rand_segments(rng, 3)
            cols = [rng.uniform(0.01, 0.2, (s.shape[-1],)
                                ).astype(np.float32) for s in segs]
            _check_merge_equals_whole(segs, True, col_scales=cols)

    def test_hypothesis_properties(self):
        """Property-based search over the same operator laws (associative,
        commutative, permutation-invariant, identity, split == whole; both
        IntMax and plain-max paths)."""
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        @st.composite
        def segments(draw, n_seg=3, max_rows=3, max_cols=9):
            rows = draw(st.integers(1, max_rows))
            segs = []
            for _ in range(n_seg):
                cols = draw(st.integers(0, max_cols))  # 0 = empty lane
                segs.append(np.asarray(draw(st.lists(
                    st.lists(st.floats(-30.0, 30.0, allow_nan=False,
                                       width=32),
                             min_size=cols, max_size=cols),
                    min_size=rows, max_size=rows)),
                    np.float32).reshape(rows, cols))
            return segs

        @settings(max_examples=40, deadline=None)
        @given(segments(n_seg=4), st.permutations(list(range(4))),
               st.booleans())
        def run(segs, perm, intmax):
            _check_merge_equals_whole(segs, intmax)
            _check_commutative(segs, intmax)
            _check_associative(segs, intmax)
            _check_permutation_invariant(segs, perm, intmax)
            if segs[0].shape[-1]:
                _check_identity_exact(segs[0], intmax)

        run()


# ---------------------------------------------------------------------------
# Kernel vs refs: parity sweeps
# ---------------------------------------------------------------------------


def _random_paged_kv(B, Hkv, D, BS, W, quantized=False):
    N = B * W + 1
    kp = jnp.asarray(_rng.normal(size=(N, Hkv, BS, D)), jnp.float32)
    vp = jnp.asarray(_rng.normal(size=(N, Hkv, BS, D)), jnp.float32)
    bt = jnp.asarray(_rng.permutation(np.arange(1, N))[:B * W].reshape(B, W),
                     jnp.int32)
    if not quantized:
        return kp, vp, bt, None, None
    kq, ksc = quantize_kv(kp)
    vq, vsc = quantize_kv(vp)
    return kq, vq, bt, ksc, vsc


# (B, Hq, Hkv, D, BS, W) and lengths: a small grid, and qwen3-4b's head
# geometry (8 KV heads, 4 query heads each, 128 wide, 16-token blocks)
# with an empty row (length 0 attends 1), a row ending mid-tile, a row at
# the full table width and a zombie row
SMALL = ((4, 8, 2, 16, 8, 7), [1, 29, 56, 0])
QWEN = ((4, 32, 8, 128, 16, 20), [1, 150, 320, 0])


def _grid_cases():
    """(geometry, T, S): the small grid across tiles and splits, and the
    qwen3-4b geometry at the derived T and explicit T 1 and 8, split 1
    and 2. T None is the tile the kernel derives from the block shape."""
    cases = [pytest.param(SMALL, T, S, id=f"{S}-{T}")
             for S in (1, 2, 3) for T in (1, 2, 4)]
    cases += [pytest.param(QWEN, T, S, id=f"qwen-{S}-{T or 'derived'}")
              for S in (1, 2) for T in (None, 1, 8)]
    return cases


class TestGroupedSplitDecodeKernel:
    @pytest.mark.parametrize("geom,T,S", _grid_cases())
    def test_matches_ref_across_tiles_and_splits(self, geom, T, S):
        """Odd lengths, mid-block and mid-tile tails, a full-width row, a
        zombie row, a one-token row — every (tile, split) layout computes
        the identical attention."""
        (B, Hq, Hkv, D, BS, W), lens = geom
        kp, vp, bt, _, _ = _random_paged_kv(B, Hkv, D, BS, W)
        q = jnp.asarray(_rng.normal(size=(B, Hq, D)),
                        jnp.float32) / np.sqrt(D)
        lens = jnp.asarray(lens, jnp.int32)
        want = paged_decode_ref(q, kp, vp, bt, lens)
        got = flash_decode_paged(q, kp, vp, bt, lens, kv_tile_blocks=T,
                                 split_k=S, interpret=True)
        sref = paged_decode_split_ref(q, kp, vp, bt, lens,
                                      kv_tile_blocks=T or 1, split_k=S)
        # row with length 0 is a zombie: kernels/split-ref emit 0 (merge
        # identity), the closed-form oracle emits a uniform average — the
        # engine masks either; compare the live rows against the oracle
        # and the zombie row against the kernel contract
        np.testing.assert_allclose(np.asarray(got)[:3],
                                   np.asarray(want)[:3], atol=1e-5)
        np.testing.assert_allclose(np.asarray(sref)[:3],
                                   np.asarray(want)[:3], atol=1e-5)
        assert np.all(np.asarray(got)[3] == 0)
        assert np.all(np.asarray(sref)[3] == 0)

    @pytest.mark.parametrize("geom,T,S", [
        pytest.param(((2, 4, 2, 16, 8, 6), [11, 41]), T, S, id=f"{T}-{S}")
        for T, S in ((1, 1), (2, 2), (4, 3))] + [
        pytest.param(((3, 32, 8, 128, 16, 20), [1, 150, 320]), T, S,
                     id=f"qwen-{T or 'derived'}-{S}")
        for T, S in ((None, 1), (8, 2))])
    def test_int8_matches_ref(self, geom, T, S):
        (B, Hq, Hkv, D, BS, W), lens = geom
        kp, vp, bt, ksc, vsc = _random_paged_kv(B, Hkv, D, BS, W,
                                                quantized=True)
        q = jnp.asarray(_rng.normal(size=(B, Hq, D)),
                        jnp.float32) / np.sqrt(D)
        lens = jnp.asarray(lens, jnp.int32)
        want = paged_decode_ref(q, kp, vp, bt, lens, k_scale=ksc,
                                v_scale=vsc)
        got = flash_decode_paged(q, kp, vp, bt, lens, k_scale=ksc,
                                 v_scale=vsc, kv_tile_blocks=T, split_k=S,
                                 interpret=True)
        sref = paged_decode_split_ref(q, kp, vp, bt, lens, k_scale=ksc,
                                      v_scale=vsc, kv_tile_blocks=T or 1,
                                      split_k=S)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(sref), np.asarray(want),
                                   atol=1e-5)

    @pytest.mark.parametrize("layered", [False, True],
                             ids=["one-layer", "layer2of3"])
    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["bf16", "int8"])
    @pytest.mark.parametrize("T,S", [
        pytest.param(T, S, id=f"T{T or 'derived'}-S{S}")
        for T in (1, 2, None) for S in (1, 3)])
    def test_live_walk_matches_refs(self, T, S, quantized, layered):
        """The live-tile walk against both references at the lengths
        where it turns: an empty row (attends 1), one block, a row ending
        on a tile edge (128 is one for T 1, 2 and the derived 4), one
        token past it, the table's full width, and a zombie row; split 3
        leaves lanes with no live tile. 32-token blocks make the derived
        T 4, so the full-width row's last tile (columns 8-11 of a 10-wide
        table) holds two dead slots."""
        B, Hq, Hkv, D, BS, W = 6, 4, 2, 16, 32, 10
        lens = jnp.asarray([1, BS, 128, 129, W * BS, 0], jnp.int32)
        layers = [_random_paged_kv(B, Hkv, D, BS, W, quantized)
                  for _ in range(3 if layered else 1)]
        bt = layers[0][2]

        def pool(i):
            a = jnp.stack([layer[i] for layer in layers])
            a = a if layered else a[0]
            return a if quantized or i > 1 else a.astype(jnp.bfloat16)

        kw = {"k_scale": pool(3), "v_scale": pool(4)} if quantized else {}
        if layered:
            kw["layer"] = 2
        q = jnp.asarray(_rng.normal(size=(B, Hq, D)),
                        jnp.float32) / np.sqrt(D)
        got = np.asarray(flash_decode_paged(
            q, pool(0), pool(1), bt, lens, kv_tile_blocks=T, split_k=S,
            interpret=True, **kw))
        want = np.asarray(paged_decode_ref(q, pool(0), pool(1), bt, lens,
                                           **kw))
        Tk = T or decode_tile_blocks(BS, Hkv, D, pool(0).dtype.itemsize)
        sref = np.asarray(paged_decode_split_ref(
            q, pool(0), pool(1), bt, lens, kv_tile_blocks=Tk, split_k=S,
            **kw))
        np.testing.assert_allclose(got[:5], want[:5], atol=1e-5)
        np.testing.assert_allclose(got, sref, atol=1e-5)
        assert np.all(got[5] == 0)

    def test_grouped_equals_legacy_per_head_kernel(self):
        """The restructure is layout-only: the grouped/tiled/split kernel
        and the retired per-head single-block kernel agree."""
        B, Hq, Hkv, D, BS, W = 2, 8, 2, 16, 8, 5
        kp, vp, bt, _, _ = _random_paged_kv(B, Hkv, D, BS, W)
        q = jnp.asarray(_rng.normal(size=(B, Hq, D)),
                        jnp.float32) / np.sqrt(D)
        lens = jnp.asarray([17, 40], jnp.int32)
        legacy = flash_decode_paged_single(q, kp, vp, bt, lens,
                                           interpret=True)
        got = flash_decode_paged(q, kp, vp, bt, lens, kv_tile_blocks=2,
                                 split_k=2, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(legacy),
                                   atol=1e-5)

    def test_oversized_tile_and_split_clamp(self):
        """T and S larger than the table clamp instead of erroring."""
        B, Hq, Hkv, D, BS, W = 1, 2, 1, 16, 8, 3
        kp, vp, bt, _, _ = _random_paged_kv(B, Hkv, D, BS, W)
        q = jnp.asarray(_rng.normal(size=(B, Hq, D)),
                        jnp.float32) / np.sqrt(D)
        lens = jnp.asarray([19], jnp.int32)
        want = paged_decode_ref(q, kp, vp, bt, lens)
        got = flash_decode_paged(q, kp, vp, bt, lens, kv_tile_blocks=16,
                                 split_k=9, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    def test_tile_rule_from_block_shape(self):
        """T from (BS, Hkv, D, itemsize): the fewest blocks of >= 128
        rows, cut to a K tile of at most 512 KiB, at least 1; the table
        width clamps it."""
        # qwen3-4b: 8 blocks of 16 rows, 8 x 16 x 128 x 2 x 8 = 256 KiB
        assert decode_tile_blocks(16, 8, 128, 2) == 8
        assert decode_tile_blocks(16, 8, 128, 1) == 8      # int8 pool
        assert decode_tile_blocks(32, 8, 128, 2) == 4      # larger blocks
        assert decode_tile_blocks(128, 8, 128, 2) == 1
        # many KV heads: 64 of 128 at bf16 is 256 KiB a block -> 2
        assert decode_tile_blocks(16, 64, 128, 2) == 2
        # a block past the budget on its own still gathers whole
        assert decode_tile_blocks(16, 128, 256, 2) == 1
        assert split_layout(3, decode_tile_blocks(16, 8, 128, 2), 1)[0] == 3

    def test_live_tile_range(self):
        """A lane walks only the tiles that hold its row's live blocks:
        ``[lane*spl, min((lane+1)*spl, ceil(n_blocks/T)))``, with
        ``n_blocks = min(ceil(len/BS), W)``; a lane past them walks
        nothing, and columns at or past ``n_blocks`` are never copied."""
        def rng_of(n, lane, spl=2, T=2, BS=16, W=6):
            return tuple(int(x) for x in live_tile_range(n, lane, spl, T,
                                                         BS, W))
        assert rng_of(40, 0) == (0, 2, 3)        # 3 blocks: 2 tiles
        assert rng_of(40, 1) == (2, 2, 3)        # second lane: empty
        assert rng_of(1, 0) == (0, 1, 1)         # one block, one tile
        assert rng_of(0, 0) == (0, 0, 0)         # nothing to walk
        assert rng_of(64, 0) == (0, 2, 4)        # ends on a tile edge
        assert rng_of(65, 1) == (2, 3, 5)        # one token past it
        assert rng_of(96, 1) == (2, 3, 6)        # the table's full width
        assert rng_of(500, 1) == (2, 3, 6)       # clamped to the table
        # traced lengths give the same range (the kernel reads SMEM)
        lo, hi, nb = jax.jit(lambda n: live_tile_range(n, 1, 2, 2, 16, 6))(
            jnp.int32(65))
        assert (int(lo), int(hi), int(nb)) == (2, 3, 5)

    @pytest.mark.parametrize("geom,T", [
        pytest.param(SMALL, 2, id="T2"),
        pytest.param(QWEN, None, id="qwen-derived")])
    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["bf16", "int8"])
    def test_layer_index_reads_that_layer(self, geom, T, quantized):
        """A pool that keeps its layer axis, read at ``layer=1``, gives bit
        for bit what the one-layer pool ``pool[1]`` gives: under the
        interpreter and through the CPU references."""
        (B, Hq, Hkv, D, BS, W), lens = geom
        layers = [_random_paged_kv(B, Hkv, D, BS, W, quantized)
                  for _ in range(3)]
        bt = layers[0][2]

        def stack(i):
            a = jnp.stack([layer[i] for layer in layers])
            return a if quantized or i > 1 else a.astype(jnp.bfloat16)

        kp, vp = stack(0), stack(1)
        sc = {"k_scale": stack(3), "v_scale": stack(4)} if quantized else {}
        sc1 = {k: v[1] for k, v in sc.items()}
        q = jnp.asarray(_rng.normal(size=(B, Hq, D)),
                        jnp.float32) / np.sqrt(D)
        lens = jnp.asarray(lens, jnp.int32)
        pairs = [
            (flash_decode_paged(q, kp, vp, bt, lens, layer=1,
                                kv_tile_blocks=T, interpret=True, **sc),
             flash_decode_paged(q, kp[1], vp[1], bt, lens,
                                kv_tile_blocks=T, interpret=True, **sc1)),
            (paged_decode_ref(q, kp, vp, bt, lens, layer=1, **sc),
             paged_decode_ref(q, kp[1], vp[1], bt, lens, **sc1)),
            (paged_decode_split_ref(q, kp, vp, bt, lens, layer=1,
                                    kv_tile_blocks=T or 1, split_k=2, **sc),
             paged_decode_split_ref(q, kp[1], vp[1], bt, lens,
                                    kv_tile_blocks=T or 1, split_k=2,
                                    **sc1))]
        for got, want in pairs:
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # and the layer read is layer 1's, not another's
        other = paged_decode_ref(q, kp[2], vp[2], bt, lens,
                                 **{k: v[2] for k, v in sc.items()})
        assert not np.array_equal(np.asarray(pairs[0][0])[:3],
                                  np.asarray(other)[:3])

    def test_layer_index_goes_with_the_layer_axis(self):
        """A layer index needs a pool with its layer axis, and a pool with
        its layer axis needs a layer index."""
        (B, Hq, Hkv, D, BS, W), lens = SMALL
        kp, vp, bt, _, _ = _random_paged_kv(B, Hkv, D, BS, W)
        q = jnp.zeros((B, Hq, D), jnp.float32)
        lens = jnp.asarray(lens, jnp.int32)
        with pytest.raises(ValueError, match="layer"):
            flash_decode_paged(q, kp, vp, bt, lens, layer=0, interpret=True)
        with pytest.raises(ValueError, match="layer"):
            flash_decode_paged(q, kp[None], vp[None], bt, lens,
                               interpret=True)

    @pytest.mark.tpu
    def test_compiled_matches_interpret(self):
        """Compiled-Pallas parity for the grouped/tiled/split grid — only
        runnable on a real TPU backend; conftest skips it elsewhere."""
        B, Hq, Hkv, D, BS, W = 2, 8, 2, 128, 32, 8
        kp, vp, bt, _, _ = _random_paged_kv(B, Hkv, D, BS, W)
        q = jnp.asarray(_rng.normal(size=(B, Hq, D)),
                        jnp.float32) / np.sqrt(D)
        lens = jnp.asarray([70, 256], jnp.int32)
        for T, S in ((4, 1), (4, 2)):
            got = flash_decode_paged(q, kp, vp, bt, lens, kv_tile_blocks=T,
                                     split_k=S)
            want = flash_decode_paged(q, kp, vp, bt, lens,
                                      kv_tile_blocks=T, split_k=S,
                                      interpret=True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-5)


# ---------------------------------------------------------------------------
# table_width_bucket: the one shared policy
# ---------------------------------------------------------------------------


class TestTableWidthBucket:
    def test_pow2_policy(self):
        assert [table_width_bucket(n) for n in (1, 2, 3, 5, 8, 9)] == \
            [1, 2, 4, 8, 8, 16]

    def test_pow2_clamps_to_nb_max_without_truncating(self):
        assert table_width_bucket(5, nb_max=6) == 6
        assert table_width_bucket(6, nb_max=6) == 6
        assert table_width_bucket(3, nb_max=6) == 4

    def test_chunk_policy_quantizes_to_chunk_blocks(self):
        assert table_width_bucket(5, chunk_blocks=2) == 6
        assert table_width_bucket(4, chunk_blocks=2) == 4
        assert table_width_bucket(1, chunk_blocks=4) == 4

    def test_bucket_sets_stay_bounded(self):
        """The warmup enumeration: every width any in-range request can
        produce collapses to a small set under either policy."""
        nb_max = 23
        pow2 = {table_width_bucket(n, nb_max=nb_max)
                for n in range(1, nb_max + 1)}
        chunk = {table_width_bucket(n, chunk_blocks=4)
                 for n in range(1, nb_max + 1)}
        assert pow2 == {1, 2, 4, 8, 16, 23}
        assert chunk == {4, 8, 12, 16, 20, 24}


# ---------------------------------------------------------------------------
# Engine: greedy equality across grid settings
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def setup():
    cfg = reduce_config(get_config("qwen3-4b"))
    params = model_fns(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def _serve(cfg, params, prompts, max_new=6, **kw):
    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_len", 96)
    eng = ContinuousEngine(cfg, params, **kw)
    hs = [eng.submit(p, max_new) for p in prompts]
    res = eng.run()
    return [res[h.req_id].tokens for h in hs], eng


class TestEngineGridSettings:
    def test_greedy_identical_across_tile_split_settings(self, setup):
        """Tile/split are layout knobs: whole-prompt-chunk, small-chunk,
        and cached engines produce identical greedy streams at any
        setting."""
        cfg, params = setup
        shared = _rng.integers(1, cfg.vocab_size, (21,)).astype(np.int32)
        prompts = [np.concatenate(
            [shared, _rng.integers(1, cfg.vocab_size, (n,))]).astype(
                np.int32) for n in (13, 30, 7)]
        base, _ = _serve(cfg, params, prompts)
        cold, _ = _serve(cfg, params, prompts, prefix_cache=False,
                         kv_tile_blocks=4, decode_split_k=2)
        tiled, e1 = _serve(cfg, params, prompts, kv_tile_blocks=4,
                           decode_split_k=2)
        chunked, _ = _serve(cfg, params, prompts, kv_tile_blocks=2,
                            decode_split_k=3, prefill_chunk=16)
        assert base == cold == tiled == chunked
        assert e1.metrics.cow_copies >= 1       # COW-fork path exercised
        assert e1.metrics.prefix_hit_tokens > 0

    def test_greedy_identical_int8_across_settings(self, setup):
        cfg, params = setup
        prompts = [_rng.integers(1, cfg.vocab_size, (n,)).astype(np.int32)
                   for n in (5, 37)]
        base, _ = _serve(cfg, params, prompts, kv_dtype="int8")
        tiled, _ = _serve(cfg, params, prompts, kv_dtype="int8",
                          kv_tile_blocks=4, decode_split_k=2,
                          prefill_chunk=16)
        assert base == tiled

    def test_interpret_kernels_run_the_grid(self, setup):
        """With cfg.interpret_kernels the engine's decode/chunk steps run
        the actual Pallas grid (tiled + split) and still match the ref
        engine's streams."""
        import dataclasses
        cfg, params = setup
        cfg_i = dataclasses.replace(cfg, interpret_kernels=True)
        prompts = [_rng.integers(1, cfg.vocab_size, (20,)).astype(np.int32)]
        base, _ = _serve(cfg, params, prompts, max_new=4)
        interp, _ = _serve(cfg_i, params, prompts, max_new=4,
                           num_blocks=32, max_batch=2, max_len=48,
                           kv_tile_blocks=2, decode_split_k=2,
                           prefill_chunk=16)
        assert base == interp

    def test_warmup_covers_tiled_buckets(self, setup):
        cfg, params = setup
        eng = ContinuousEngine(cfg, params, block_size=8, num_blocks=32,
                               max_batch=2, max_len=48, prefill_chunk=16,
                               kv_tile_blocks=2, decode_split_k=2)
        eng.warmup()
        assert eng.metrics.steps == 0
        h = eng.submit(
            _rng.integers(1, cfg.vocab_size, (20,)).astype(np.int32), 4)
        res = eng.run()
        assert len(res[h.req_id].tokens) == 4

    def test_rejects_bad_grid_settings(self, setup):
        cfg, params = setup
        with pytest.raises(ValueError):
            ContinuousEngine(cfg, params, kv_tile_blocks=0)
        with pytest.raises(ValueError):
            ContinuousEngine(cfg, params, decode_split_k=0)


@pytest.mark.slow
class TestBenchSmoke:
    def test_decode_paged_bench_smoke(self):
        """The benchmark's CI mode: kernel parity + five-path engine
        greedy equality on a tiny workload; speed reported, not gated."""
        import pathlib
        import sys
        root = pathlib.Path(__file__).resolve().parent.parent
        sys.path.insert(0, str(root / "benchmarks"))
        try:
            import decode_paged_bench
            ratio = decode_paged_bench.main(["--smoke"])
        finally:
            sys.path.pop(0)
        assert ratio > 0
