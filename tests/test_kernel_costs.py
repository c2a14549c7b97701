"""Kernel cost observatory: the analytic launch-cost model pinned
byte-exact against the ref layer's measuring oracles, the grid planner's
argmin/memoization properties, and the engine's per-step integration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_decode_paged.ref import (decode_gather_oracle,
                                                  decode_tile_blocks,
                                                  split_layout)
from repro.kernels.flash_prefill_paged.ref import prefill_gather_oracle
from repro.models.registry import get_config, model_fns, reduce_config
from repro.serve import (AUTOTUNE_MODES, ContinuousEngine, CostParams,
                         GridPlanner, Telemetry, decode_launch_cost,
                         default_candidates, estimate_seconds,
                         prefill_launch_cost)

_rng = np.random.default_rng(11)

Hq, Hkv, D, BS = 8, 2, 64, 16
SHAPE = dict(n_q_heads=Hq, n_kv_heads=Hkv, head_dim=D, block_size=BS)


def _pools(n_blocks, dtype):
    if dtype == "int8":
        k = _rng.integers(-127, 128, (n_blocks, Hkv, BS, D)).astype(np.int8)
        v = _rng.integers(-127, 128, (n_blocks, Hkv, BS, D)).astype(np.int8)
        ks = _rng.random((n_blocks, Hkv, BS)).astype(np.float32)
        return (jnp.asarray(k), jnp.asarray(v),
                jnp.asarray(ks), jnp.asarray(ks))
    k = _rng.standard_normal((n_blocks, Hkv, BS, D))
    arr = jnp.asarray(k, dtype=jnp.dtype(dtype))
    return arr, arr, None, None


def _table(B, W, n_blocks, lengths):
    """Exact-cover tables: row i holds ceil(len/BS) real entries, rest 0.
    A row's blocks are distinct, as the pool's are."""
    bt = np.zeros((B, W), np.int32)
    for i, ln in enumerate(lengths):
        nb = min(-(-int(ln) // BS), W)
        bt[i, :nb] = _rng.choice(np.arange(1, n_blocks), nb, replace=False)
    return jnp.asarray(bt)


class TestDecodeModelMatchesOracle:
    @pytest.mark.parametrize("dtype", ["float32", "int8"])
    @pytest.mark.parametrize("tile,split", [(1, 1), (2, 1), (4, 1),
                                            (1, 2), (2, 2), (4, 3),
                                            (16, 1), (1, 4), (None, 1),
                                            (None, 2)])
    def test_gather_waste_steps_exact(self, dtype, tile, split):
        B, W, n_blocks = 5, 12, 64
        lengths = np.array([1, 17, 64, 190, 7], np.int64)
        k, v, ks, vs = _pools(n_blocks, dtype)
        bt = _table(B, W, n_blocks, lengths)
        oracle = decode_gather_oracle(k, v, bt, lengths,
                                      kv_tile_blocks=tile, split_k=split,
                                      k_scale=ks, v_scale=vs)
        model = decode_launch_cost(lengths, W, kv_tile_blocks=tile,
                                   split_k=split, kv_dtype=dtype, **SHAPE)
        assert model.gather_bytes == oracle["gather_bytes"]
        assert model.waste_bytes == oracle["waste_bytes"] == 0
        assert model.useful_bytes == oracle["useful_bytes"]
        assert model.grid_steps == oracle["grid_steps"]
        assert model.steps_per_lane == oracle["steps_per_lane"]
        T = tile or decode_tile_blocks(BS, Hkv, D, k.dtype.itemsize)
        _, S, _, _ = split_layout(W, T, split)
        assert model.grid_steps == model.lanes == B * S
        # the walk visits each row's live tiles once, over all its lanes
        assert oracle["tile_steps"] == sum(
            -(-min(-(-int(n) // BS), W) // min(T, W)) for n in lengths)

    def test_random_geometry_sweep(self):
        for _ in range(25):
            B = int(_rng.integers(1, 7))
            W = int(_rng.integers(1, 40))
            tile = int(_rng.integers(1, 9))
            split = int(_rng.integers(1, 5))
            lengths = _rng.integers(1, W * BS + 1, (B,))
            k, v, _, _ = _pools(48, "float32")
            bt = _table(B, W, 48, lengths)
            oracle = decode_gather_oracle(k, v, bt, lengths,
                                          kv_tile_blocks=tile,
                                          split_k=split)
            model = decode_launch_cost(lengths, W, kv_tile_blocks=tile,
                                       split_k=split, **SHAPE)
            assert model.gather_bytes == oracle["gather_bytes"]
            assert model.waste_bytes == oracle["waste_bytes"] == 0
            assert model.grid_steps == oracle["grid_steps"]
            assert model.steps_per_lane == oracle["steps_per_lane"]

    def test_waste_zero_iff_no_padding(self):
        """The live walk copies no padding: waste is 0 whether or not
        the rows fill the table and the tiles, and a shortfall (a freed
        block, or a padded tile) only shrinks what is copied."""
        B, W = 3, 8
        lengths = np.full((B,), W * BS, np.int64)
        model = decode_launch_cost(lengths, W, kv_tile_blocks=2, split_k=2,
                                   **SHAPE)
        assert model.waste_bytes == 0
        short = lengths.copy()
        short[0] -= BS
        shorter = decode_launch_cost(short, W, kv_tile_blocks=2, split_k=2,
                                     **SHAPE)
        assert shorter.waste_bytes == 0
        assert model.gather_bytes - shorter.gather_bytes == \
            Hkv * 2 * BS * D * 4
        assert decode_launch_cost(lengths, W, kv_tile_blocks=3, split_k=1,
                                  **SHAPE).waste_bytes == 0

    def test_dead_steps_copy_nothing(self):
        """The (B, S) grid walks live tiles only: widening the table past
        every row's last live tile adds the table's int32 entries, and no
        grid step, tile step or block copy."""
        lengths = np.array([1, 17, 64, 190, 7], np.int64)
        narrow = decode_launch_cost(lengths, 16, kv_tile_blocks=4, **SHAPE)
        wide = decode_launch_cost(lengths, 64, kv_tile_blocks=4, **SHAPE)
        assert wide.gather_bytes - narrow.gather_bytes == 5 * (64 - 16) * 4
        assert narrow.grid_steps == wide.grid_steps == narrow.lanes == 5
        # the 190-token row's 12 live blocks are 3 tiles of 4
        assert narrow.steps_per_lane == wide.steps_per_lane == 3
        assert narrow.flops == wide.flops
        # one copy moves a block's every KV head, K and V
        assert narrow.tile_bytes == 4 * Hkv * 2 * BS * D * 4

    def test_int8_scale_siblings_counted(self):
        lengths = np.array([40, 8], np.int64)
        f32 = decode_launch_cost(lengths, 4, kv_tile_blocks=1,
                                 kv_dtype="float32", **SHAPE)
        i8 = decode_launch_cost(lengths, 4, kv_tile_blocks=1,
                                kv_dtype="int8", **SHAPE)
        # int8 blocks: quarter the values + the f32 scale rows; each row
        # copies its live blocks once (3 + 1 whole blocks of Hkv heads),
        # each with a K and a V scale row of every head's BS scales padded
        # to 128 lanes, and both move the same 2 x 4 int32 table
        table = 2 * 4 * 4
        rows = -(-Hkv * BS // 128) * 128
        assert i8.gather_bytes - table == \
            (f32.gather_bytes - table) // 4 + (3 + 1) * 2 * rows * 4

    def test_scaled_multiplies_extensive_fields_only(self):
        c = decode_launch_cost(np.array([33]), 4, **SHAPE)
        s = c.scaled(3)
        assert s.gather_bytes == 3 * c.gather_bytes
        assert s.flops == 3 * c.flops
        assert s.grid_steps == 3 * c.grid_steps
        assert s.tile_bytes == c.tile_bytes
        assert s.vmem_bytes == c.vmem_bytes
        d = c.to_dict()
        assert d["useful_bytes"] == c.gather_bytes - c.waste_bytes

    def test_unknown_dtype_rejected(self):
        with pytest.raises(ValueError, match="kv_dtype"):
            decode_launch_cost(np.array([8]), 2, kv_dtype="fp4", **SHAPE)


class TestPrefillModelMatchesOracle:
    @pytest.mark.parametrize("dtype", ["float32", "int8"])
    @pytest.mark.parametrize("tile", [1, 2, 4])
    @pytest.mark.parametrize("q_len,block_q", [(32, 128), (200, 128),
                                               (64, 32)])
    def test_gather_waste_steps_exact(self, dtype, tile, q_len, block_q):
        B, n_blocks = 3, 64
        pos0 = np.array([0, 48, 16], np.int64)
        cover = [-(-int(p + q_len) // BS) for p in pos0]
        W = max(cover) + 2                       # some rows padded
        k, v, ks, vs = _pools(n_blocks, dtype)
        bt = _table(B, W, n_blocks, [c * BS for c in cover])
        oracle = prefill_gather_oracle(k, v, bt, pos0, q_len,
                                       kv_tile_blocks=tile, block_q=block_q,
                                       cover_blocks=cover,
                                       k_scale=ks, v_scale=vs)
        model = prefill_launch_cost(q_len, pos0, cover, W,
                                    kv_tile_blocks=tile, block_q=block_q,
                                    kv_dtype=dtype, **SHAPE)
        assert model.gather_bytes == oracle["gather_bytes"]
        assert model.waste_bytes == oracle["waste_bytes"]
        assert model.useful_bytes == oracle["useful_bytes"]
        assert model.grid_steps == oracle["grid_steps"]

    def test_waste_zero_iff_exact_cover(self):
        pos0, q_len = [0], 4 * BS
        cover = [4]
        model = prefill_launch_cost(q_len, pos0, cover, 4, **SHAPE)
        assert model.waste_bytes == 0
        padded = prefill_launch_cost(q_len, pos0, cover, 6, **SHAPE)
        assert padded.waste_bytes > 0

    def test_misaligned_rows_rejected(self):
        with pytest.raises(ValueError, match="align"):
            prefill_launch_cost(32, [0, 1], [2], 4, **SHAPE)


class TestEstimateSeconds:
    def test_monotone_in_length(self):
        # compute-bound machine point: the walk's FLOPs grow with the
        # live tiles (its gather bytes do too)
        p = CostParams(flops_per_s=5e10)
        costs = [decode_launch_cost(np.array([ln]), 16, **SHAPE)
                 for ln in (8, 64, 200)]
        secs = [estimate_seconds(c, p) for c in costs]
        assert secs == sorted(secs) and secs[0] < secs[-1]

    def test_split_k_helps_long_row_with_cores(self):
        # one long row: split-K halves the sequential walk when there are
        # cores to absorb the extra lanes
        lengths = np.array([64 * BS], np.int64)
        p = CostParams(cores=8)
        t1 = estimate_seconds(decode_launch_cost(lengths, 64, **SHAPE), p)
        t4 = estimate_seconds(decode_launch_cost(lengths, 64, split_k=4,
                                                 **SHAPE), p)
        assert t4 < t1


class TestGridPlanner:
    CANDS = [(1, 1), (2, 1), (4, 1), (2, 2)]

    def _planner(self, **kw):
        return GridPlanner(self.CANDS, kv_dtype="float32", **SHAPE, **kw)

    def test_argmin_never_loses_to_any_fixed_candidate(self):
        pl = self._planner()
        for _ in range(20):
            B = int(_rng.integers(1, 6))
            W = int(_rng.integers(1, 33))
            lengths = _rng.integers(1, W * BS + 1, (B,))
            dec = pl.plan_decode(lengths, W)
            for (t, s) in self.CANDS:
                c = decode_launch_cost(lengths, W, kv_tile_blocks=t,
                                       split_k=s, **SHAPE)
                assert dec.predicted_s <= estimate_seconds(
                    c, pl.cost_params) + 1e-15
            assert (dec.kv_tile_blocks, dec.split_k) in self.CANDS
            assert len(dec.considered) == len(self.CANDS)

    def test_memoizes_on_block_counts_not_raw_lengths(self):
        pl = self._planner()
        d1 = pl.plan_decode(np.array([17, 33]), 8)
        # same per-row block counts (ceil/BS), different raw lengths
        d2 = pl.plan_decode(np.array([20, 44]), 8)
        assert d2 is d1
        assert len(pl._cache) == 1
        d3 = pl.plan_decode(np.array([17, 49]), 8)   # crosses a block
        assert d3 is not d1

    def test_decisions_recorded_to_registry(self):
        from repro.serve import MetricRegistry
        reg = MetricRegistry()
        pl = self._planner(registry=reg)
        pl.plan_decode(np.array([40]), 4)
        pl.plan_decode(np.array([40]), 4)            # cache hit still counts
        assert reg.get("autotune_decisions_total").value == 2
        assert sum(v for k, v in pl.summary().items()) == 2
        pl.observe_measured(pl.plan_decode(np.array([40]), 4), 1e-3)
        assert reg.get("autotune_pred_over_measured").value > 0

    def test_default_candidates_closed_and_deduped(self):
        cands = default_candidates(4, 2)
        assert set(cands) == {(1, 1), (4, 1), (1, 2), (4, 2)}
        assert default_candidates(1, 1) == ((1, 1),)
        with pytest.raises(ValueError):
            GridPlanner([(0, 1)], kv_dtype="float32", **SHAPE)


class TestEngineIntegration:
    @pytest.fixture(scope="class")
    def setup(self):
        cfg = reduce_config(get_config("qwen3-4b"))
        params = model_fns(cfg).init(jax.random.PRNGKey(0))
        return cfg, params

    def _run(self, cfg, params, tel=None, **kw):
        eng = ContinuousEngine(cfg, params, block_size=8, num_blocks=48,
                               max_batch=4, max_len=64, telemetry=tel,
                               **kw)
        rng = np.random.default_rng(5)
        hs = [eng.submit(rng.integers(1, 100, (n,)).astype(np.int32), 5)
              for n in (9, 21, 13)]
        res = eng.run()
        return [res[h.req_id].tokens for h in hs], eng

    def test_autotune_modes_same_tokens_and_decisions(self, setup):
        cfg, params = setup
        streams = {}
        for mode in AUTOTUNE_MODES:
            toks, eng = self._run(cfg, params, autotune=mode,
                                  kv_tile_blocks=2, decode_split_k=2)
            streams[mode] = toks
            if mode == "off":
                assert eng.planner is None
            elif mode == "per-step":
                assert sum(eng.planner.summary().values()) > 0
        assert streams["off"] == streams["static"] == streams["per-step"]

    def test_invalid_mode_rejected(self, setup):
        cfg, params = setup
        with pytest.raises(ValueError, match="autotune"):
            ContinuousEngine(cfg, params, block_size=8, num_blocks=48,
                             max_batch=4, max_len=64, autotune="always")

    def test_kernel_cost_metrics_published(self, setup):
        cfg, params = setup
        from repro.serve import ManualClock
        tel = Telemetry(clock=ManualClock(tick=1e-4))
        _, eng = self._run(cfg, params, tel=tel, autotune="per-step")
        reg = tel.registry
        dma = reg.get("kernel_dma_bytes_total").value
        waste = reg.get("kernel_waste_bytes_total").value
        assert dma > 0 and reg.get("kernel_flops_total").value > 0
        assert 0 <= waste < dma
        assert reg.get("kernel_launch_dma_bytes").count > 0
        assert reg.get("autotune_decisions_total").value > 0
        # decode timeline slices carry the per-phase cost stamp
        decode_evs = [e for e in tel.timeline.events
                      if e["name"] == "decode"]
        assert decode_evs
        for e in decode_evs:
            assert e["args"]["dma_bytes"] > 0
            assert e["args"]["flops"] > 0
        # counter totals == sum over the timeline-stamped phases that ran
        # a paged kernel: the decode and prefill-chunk slices
        kernel_evs = [e for e in tel.timeline.events
                      if e["name"] in ("decode", "prefill-chunk")]
        assert sum(e["args"]["dma_bytes"] for e in kernel_evs) == dma

    def test_engine_decode_cost_matches_direct_model(self, setup):
        cfg, params = setup
        from repro.serve import ManualClock
        tel = Telemetry(clock=ManualClock(tick=1e-4))
        _, eng = self._run(cfg, params, tel=tel)
        ev = [e for e in tel.timeline.events if e["name"] == "decode"][0]
        # one decode launch re-modeled from the stamped geometry must obey
        # the accounting identity dma >= waste and layers-scaling
        assert ev["args"]["dma_bytes"] % cfg.n_layers == 0
        assert ev["args"]["waste_bytes"] <= ev["args"]["dma_bytes"]
