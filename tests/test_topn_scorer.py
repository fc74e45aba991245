"""DeviceTopNScorer — device-resident serving scorer (pio_tpu/ops/topn.py).

Device and host paths must agree exactly (same factors, same queries);
the device path is forced on the simulated CPU backend via prefer_device.
"""

import pickle

import numpy as np
import pytest

from pio_tpu.ops.topn import DeviceTopNScorer, _bucket


def _factors(n_rows=37, n_cols=53, k=8, seed=0):
    rng = np.random.default_rng(seed)
    return (
        rng.normal(size=(n_rows, k)).astype(np.float32),
        rng.normal(size=(n_cols, k)).astype(np.float32),
    )


def test_bucket():
    assert _bucket(1, 512) == 1
    assert _bucket(3, 512) == 4
    assert _bucket(16, 512) == 16
    assert _bucket(700, 512) == 512


@pytest.mark.parametrize("device", [False, True])
def test_topn_matches_naive(device):
    rows, cols = _factors()
    s = DeviceTopNScorer(rows, cols, prefer_device=device)
    codes = np.array([0, 3, 36, 7], np.int32)
    idx, vals = s.top_n_batch(codes, 5)
    assert idx.shape == (4, 5) and vals.shape == (4, 5)
    full = rows[codes] @ cols.T
    for b in range(4):
        want = np.argsort(-full[b])[:5]
        np.testing.assert_array_equal(idx[b], want)
        np.testing.assert_allclose(vals[b], full[b][want], rtol=1e-5)


@pytest.mark.parametrize("device", [False, True])
def test_topn_exclusion(device):
    rows, cols = _factors()
    s = DeviceTopNScorer(rows, cols, prefer_device=device)
    codes = np.array([1, 2], np.int32)
    full = rows[codes] @ cols.T
    # exclude each row's natural top-1; pad second row's slots with the
    # sentinel (>= n_cols)
    top1 = np.argsort(-full, axis=1)[:, 0]
    excl = np.stack([
        [top1[0], int(np.argsort(-full[0])[1])],
        [top1[1], s.n_cols],  # sentinel slot
    ]).astype(np.int32)
    idx, vals = s.top_n_batch(codes, 3, exclude=excl)
    assert top1[0] not in idx[0]
    assert int(np.argsort(-full[0])[1]) not in idx[0]
    assert top1[1] not in idx[1]
    # row 1 keeps its rank-2 item (only top-1 excluded)
    assert int(np.argsort(-full[1])[1]) == idx[1][0]


@pytest.mark.parametrize("device", [False, True])
def test_large_batch_chunks_and_n_clamp(device):
    rows, cols = _factors(n_rows=600, n_cols=17)
    s = DeviceTopNScorer(rows, cols, prefer_device=device)
    codes = np.arange(600, dtype=np.int32) % 600
    # n > n_cols clamps to n_cols; B > _MAX_BATCH_BUCKET chunks internally
    idx, vals = s.top_n_batch(codes, 99)
    assert idx.shape == (600, 17)
    full = rows[codes] @ cols.T
    np.testing.assert_array_equal(idx[123], np.argsort(-full[123]))


@pytest.mark.parametrize("device", [False, True])
def test_pairs_and_scores(device):
    rows, cols = _factors()
    s = DeviceTopNScorer(rows, cols, prefer_device=device)
    rc = np.array([0, 5], np.int32)
    cc = np.array([7, 9], np.int32)
    np.testing.assert_allclose(
        s.score_pairs(rc, cc),
        np.einsum("bk,bk->b", rows[rc], cols[cc]),
        rtol=1e-5,
    )
    np.testing.assert_allclose(
        s.scores_batch(rc), rows[rc] @ cols.T, rtol=1e-5
    )


def test_adaptive_routing_by_link_speed():
    """Auto mode routes by batch size: a slow link sends small batches to
    the host mirror; a fast link sends everything to the device."""
    rows, cols = _factors()
    slow = DeviceTopNScorer(rows, cols, link_rtt_s=10.0)  # remote link
    assert slow.on_device
    assert slow.min_device_batch > 1_000  # B=1 stays on host
    assert not slow._route_to_device(1)
    fast = DeviceTopNScorer(rows, cols, link_rtt_s=0.0)  # local PCIe/ICI
    assert fast.min_device_batch == 1
    assert fast._route_to_device(1)
    # both produce identical results for the same query
    codes = np.array([4, 9], np.int32)
    i1, v1 = slow.top_n_batch(codes, 3)
    i2, v2 = fast.top_n_batch(codes, 3)
    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_allclose(v1, v2, rtol=1e-5)
    # which route answered is on the record, with what the probe measured
    info = slow.route_info()
    assert info["routes"] == {"device": 0, "host": 1}
    assert info["mode"] == "auto" and info["linkRttS"] == 10.0
    assert info["hostRowS"] > 0
    assert info["minDeviceBatch"] == slow.min_device_batch
    assert fast.route_info()["routes"] == {"device": 1, "host": 0}


def test_route_counters_cover_every_public_call():
    """scores_batch and score_pairs route on their own thresholds; each
    call bumps the route it took, and forced modes skip the probe."""
    rows, cols = _factors()
    s = DeviceTopNScorer(rows, cols, link_rtt_s=1e-3)
    small = s.min_pair_batch - 1
    s.score_pairs(np.zeros(small, np.int32), np.zeros(small, np.int32))
    s.scores_batch(np.array([1], np.int32))
    assert s.route_info()["routes"] == {"device": 0, "host": 2}
    s.score_pairs([], [])  # nothing scored, nothing counted
    assert s.route_info()["routes"] == {"device": 0, "host": 2}
    dev = DeviceTopNScorer(rows, cols, prefer_device=True)
    dev.score_pairs([1], [2])
    dev.scores_batch(np.array([1], np.int32))
    dev.top_n_batch(np.array([1], np.int32), 3)
    info = dev.route_info()
    assert info["routes"] == {"device": 3, "host": 0}
    assert info["mode"] == "device" and info["linkRttS"] is None
    host = DeviceTopNScorer(rows, cols, prefer_device=False).route_info()
    assert host["mode"] == "host" and host["minDeviceBatch"] is None


def test_env_override_forces_host(monkeypatch):
    monkeypatch.setenv("PIO_TPU_SERVE_DEVICE", "0")
    rows, cols = _factors()
    s = DeviceTopNScorer(rows, cols)
    assert not s.on_device
    monkeypatch.setenv("PIO_TPU_SERVE_DEVICE", "1")
    s = DeviceTopNScorer(rows, cols)
    assert s.on_device and s.min_device_batch == 1


def test_pair_routing_stays_on_host_for_small_batches():
    """Pair dots are ~n_cols× cheaper than a score row on host, so their
    device break-even batch is much larger."""
    rows, cols = _factors()
    s = DeviceTopNScorer(rows, cols, link_rtt_s=1e-3)
    assert s.min_pair_batch >= s.min_device_batch
    np.testing.assert_allclose(
        s.score_pairs([1], [2]), [float(rows[1] @ cols[2])], rtol=1e-5
    )


def test_predict_num_zero_returns_empty():
    """query.num <= 0 must yield an empty result on the online path too
    (parity with the pre-scorer behavior and with batch_predict)."""
    from pio_tpu.data.bimap import BiMap
    from pio_tpu.models.als import ALSFactors
    from pio_tpu.templates.recommendation import ALSAlgorithm, ALSModel, Query

    rows, cols = _factors()
    m = ALSModel(
        ALSFactors(rows, cols),
        BiMap.string_int([f"u{i}" for i in range(len(rows))]),
        BiMap.string_int([f"i{i}" for i in range(len(cols))]),
    )
    algo = ALSAlgorithm(None)
    assert algo.predict(m, Query(user="u1", num=0)).item_scores == ()
    assert dict(algo.batch_predict(
        m, [(0, Query(user="u1", num=0))]
    ))[0].item_scores == ()


def test_pairs_beyond_chunk_cap():
    """score_pairs must chunk, not crash, past the 2^20 dispatch cap."""
    rows, cols = _factors(n_rows=50, n_cols=60)
    s = DeviceTopNScorer(rows, cols, prefer_device=True)
    rng = np.random.default_rng(1)
    B = (1 << 20) + 3
    rc = rng.integers(0, 50, B).astype(np.int32)
    cc = rng.integers(0, 60, B).astype(np.int32)
    got = s.score_pairs(rc, cc)
    assert got.shape == (B,)
    np.testing.assert_allclose(
        got[-5:], np.einsum("bk,bk->b", rows[rc[-5:]], cols[cc[-5:]]),
        rtol=1e-5,
    )


def test_exclusion_widths_share_compiles():
    """Exclusion width is bucketed: different raw E values give the same
    (correct) answer and reuse pow-2-bucketed jitted shapes."""
    rows, cols = _factors()
    s = DeviceTopNScorer(rows, cols, prefer_device=True)
    codes = np.array([3], np.int32)
    full = rows[3] @ cols.T
    top = np.argsort(-full)
    for E in (1, 2, 3, 5, 9):
        excl = np.array([top[:E]], np.int32)
        idx, _ = s.top_n_batch(codes, 3, exclude=excl)
        np.testing.assert_array_equal(idx[0], top[E:E + 3])


def test_batch_negative_num_matches_online():
    """num <= 0 gives an empty result on BOTH serving paths."""
    from pio_tpu.data.bimap import BiMap
    from pio_tpu.models.als import ALSFactors
    from pio_tpu.templates.recommendation import ALSAlgorithm, ALSModel, Query

    rows, cols = _factors()
    m = ALSModel(
        ALSFactors(rows, cols),
        BiMap.string_int([f"u{i}" for i in range(len(rows))]),
        BiMap.string_int([f"i{i}" for i in range(len(cols))]),
    )
    algo = ALSAlgorithm(None)
    q = Query(user="u2", num=-1)
    assert algo.predict(m, q).item_scores == ()
    got = dict(algo.batch_predict(m, [(0, Query(user="u1", num=5)), (1, q)]))
    assert got[1].item_scores == ()
    assert len(got[0].item_scores) == 5


def test_empty_batch():
    rows, cols = _factors()
    s = DeviceTopNScorer(rows, cols, prefer_device=True)
    idx, vals = s.top_n_batch(np.empty(0, np.int32), 5)
    assert idx.shape == (0, 5)


def test_rank_mismatch_rejected():
    rows, cols = _factors()
    with pytest.raises(ValueError):
        DeviceTopNScorer(rows, cols[:, :4])


def test_empty_factor_tables():
    """Zero-row/zero-col tables must construct (no host-probe indexing)
    and score to empty results instead of raising."""
    rows, cols = _factors()
    for r, c in [
        (np.empty((0, rows.shape[1]), np.float32), cols),
        (rows, np.empty((0, rows.shape[1]), np.float32)),
    ]:
        s = DeviceTopNScorer(r, c)  # auto mode: would probe if unguarded
        assert not s.on_device
        if s.n_cols == 0:
            idx, vals = s.top_n_batch(np.empty(0, np.int32), 5)
            assert idx.shape == (0, 0) and vals.shape == (0, 0)
        assert s.score_pairs(
            np.empty(0, np.int32), np.empty(0, np.int32)
        ).shape == (0,)


def test_model_pickle_drops_scorer():
    """Deployed models lazily cache a scorer; serialization must drop the
    device handles (they rebuild on the next host)."""
    from pio_tpu.data.bimap import BiMap
    from pio_tpu.models.als import ALSFactors
    from pio_tpu.templates.recommendation import ALSModel

    rows, cols = _factors()
    m = ALSModel(
        ALSFactors(rows, cols),
        BiMap.string_int([f"u{i}" for i in range(len(rows))]),
        BiMap.string_int([f"i{i}" for i in range(len(cols))]),
    )
    m.scorer(warmup=False)
    assert "_scorer" in m.__dict__
    m2 = pickle.loads(pickle.dumps(m))
    assert "_scorer" not in m2.__dict__
    # and the revived model still serves
    idx, vals = m2.scorer().top_n_batch(np.array([0], np.int32), 3)
    assert idx.shape == (1, 3)


def test_prepare_for_serving_attaches_scorer():
    """Engine.algorithms_with_models runs the deploy-time serving prep."""
    from pio_tpu.controller.engine import EngineParams
    from pio_tpu.data.bimap import BiMap
    from pio_tpu.models.als import ALSFactors
    from pio_tpu.templates.recommendation import (
        ALSModel, recommendation_engine,
    )

    rows, cols = _factors()
    model = ALSModel(
        ALSFactors(rows, cols),
        BiMap.string_int([f"u{i}" for i in range(len(rows))]),
        BiMap.string_int([f"i{i}" for i in range(len(cols))]),
    )
    engine = recommendation_engine()
    ep = EngineParams(algorithm_params_list=(("als", None),))
    pairs = engine.algorithms_with_models(ep, [model])
    assert "_scorer" in pairs[0][1].__dict__


class TestNativeHostScorer:
    """Fused native scan-and-select vs the numpy reference path."""

    @pytest.fixture(autouse=True)
    def _require_native(self):
        # without the toolchain both paths would be numpy — a parity
        # test against itself proves nothing
        from pio_tpu.native import NativeUnavailable, topn_host_lib

        try:
            topn_host_lib()
        except NativeUnavailable:
            pytest.skip("no C++ toolchain: native scorer not buildable")

    def test_parity_with_numpy_path(self):
        rng = np.random.default_rng(7)
        rows = rng.normal(size=(300, 8)).astype(np.float32)
        cols = rng.normal(size=(500, 8)).astype(np.float32)
        s = DeviceTopNScorer(rows, cols, prefer_device=False)
        codes = rng.integers(0, 300, 8).astype(np.int32)
        for n in (1, 5, 10, 500):  # incl. n == n_cols (full sort)
            i_nat, v_nat = s.top_n_batch(codes, n)
            native = s._top_n_host_native
            s._top_n_host_native = lambda c, k: None
            try:
                i_np, v_np = s.top_n_batch(codes, n)
            finally:
                s._top_n_host_native = native
            assert np.array_equal(i_nat, i_np), n
            assert np.allclose(v_nat, v_np), n

    def test_nan_scores_do_not_crash(self):
        """NaN factors (diverged model) must rank last, not crash the
        comparator (strict-weak-ordering UB in std::sort)."""
        rng = np.random.default_rng(10)
        rows = np.ones((4, 4), np.float32)
        cols = rng.normal(size=(200, 4)).astype(np.float32)
        cols[::3] = np.nan  # third of the table poisoned
        s = DeviceTopNScorer(rows, cols, prefer_device=False)
        idx, vals = s.top_n_batch(np.array([0], np.int32), 10)
        assert np.isfinite(vals).all()  # NaN rows never outrank real ones
        assert not (set(idx.flat) & set(range(0, 200, 3)))

    def test_exclusions_use_numpy_path(self):
        """The native kernel doesn't handle exclusions — masked queries
        must still produce masked results (numpy path)."""
        rng = np.random.default_rng(8)
        rows = rng.normal(size=(20, 4)).astype(np.float32)
        cols = rng.normal(size=(30, 4)).astype(np.float32)
        s = DeviceTopNScorer(rows, cols, prefer_device=False)
        codes = np.arange(3, dtype=np.int32)
        excl = np.tile(np.array([[0, 1, 2]], np.int32), (3, 1))
        idx, _ = s.top_n_batch(codes, 5, exclude=excl)
        assert not (set(idx.flat) & {0, 1, 2})

    def test_rank_zero_degenerate(self):
        """Rank-0 factor tables (0 == 0 passes the mismatch check) must
        score everything 0 and rank by index — no out-of-bounds read."""
        rows = np.empty((3, 0), np.float32)
        cols = np.empty((5, 0), np.float32)
        s = DeviceTopNScorer(rows, cols, prefer_device=False)
        idx, vals = s.top_n_batch(np.array([0, 2], np.int32), 3)
        assert np.array_equal(idx, [[0, 1, 2], [0, 1, 2]])
        assert np.all(vals == 0.0)

    def test_tiny_table_smaller_than_topn(self):
        rng = np.random.default_rng(9)
        rows = rng.normal(size=(4, 4)).astype(np.float32)
        cols = rng.normal(size=(3, 4)).astype(np.float32)
        s = DeviceTopNScorer(rows, cols, prefer_device=False)
        idx, vals = s.top_n_batch(np.array([1], np.int32), 10)
        assert idx.shape == (1, 3)  # clamped to n_cols
        assert sorted(idx[0].tolist()) == [0, 1, 2]
