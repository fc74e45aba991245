"""ALS model tests: reconstruction quality, mesh-vs-local parity, implicit
mode, edge cases. Runs on the simulated 8-device CPU mesh (conftest)."""

import numpy as np
import pytest

from pio_tpu.models.als import (
    ALSConfig, _train_mesh_host_packed, top_n, train_als,
)
from pio_tpu.parallel.context import ComputeContext


@pytest.fixture(scope="module")
def synthetic():
    rng = np.random.default_rng(0)
    U, I, K = 60, 40, 4
    P = rng.normal(size=(U, K))
    Q = rng.normal(size=(I, K))
    R = P @ Q.T
    mask = rng.random((U, I)) < 0.6
    u_idx, i_idx = np.nonzero(mask)
    return dict(U=U, I=I, R=R, mask=mask, u=u_idx, i=i_idx, r=R[u_idx, i_idx])


CFG = ALSConfig(rank=8, iterations=12, reg=0.01, blocks_per_chunk=64)


#: how the trainers call ``device_pack``
_PACK_FORMS = ["shuffled", "shuffled_counts", "sorted", "chunk"]

#: (degree sequence from a generator, block width): what a copy of runs
#: can get wrong. A run is fetched as whole rows of 128 edges.
_PACK_LAYOUTS = [
    pytest.param(lambda g: np.bincount(g.integers(0, 80, 5000),
                                       minlength=80), 16, id="random-w16"),
    pytest.param(lambda g: [0, 0, 1, 0], 8, id="one-edge"),
    pytest.param(lambda g: np.bincount(g.integers(0, 4, 64), minlength=4),
                 8, id="dense-w8"),
    pytest.param(lambda g: np.bincount(g.integers(0, 200, 97),
                                       minlength=200), 8, id="sparse-w8"),
    # starts at every offset of a row, at every width the rule picks
    *[pytest.param(lambda g: g.integers(0, 150, 60), w, id=f"skewed-w{w}")
      for w in (8, 16, 32, 64)],
    pytest.param(lambda g: np.arange(1, 140), 64, id="every-offset-w64"),
    # a run over a whole row, and one that touches three
    pytest.param(lambda g: g.integers(0, 400, 30), 128, id="skewed-w128"),
    pytest.param(lambda g: g.integers(0, 700, 20), 200, id="skewed-w200"),
    # 384 edges: the last run ends where a row of 128 ends, so the row
    # after it is padding alone
    pytest.param(lambda g: [100, 156, 128], 64, id="ends-on-a-row"),
    # 385 edges: the last run is the list's last edge, alone in its row
    pytest.param(lambda g: [129, 250, 6], 8, id="ends-on-the-last-edge"),
    # the last run starts on a row's last lane
    pytest.param(lambda g: [255, 40], 32, id="starts-on-lane-127"),
    pytest.param(lambda g: [16, 48, 0, 32, 16], 16,
                 id="counts-are-multiples-of-the-width"),
    pytest.param(lambda g: [0, 0, 5, 0, 0, 0, 17, 3, 0, 0], 8,
                 id="empty-front-middle-end"),
    pytest.param(lambda g: [0, 0, 300, 0], 16, id="one-entity-holds-all"),
    pytest.param(lambda g: [1000], 64, id="one-entity-alone"),
]


class TestALS:
    def test_reconstructs_observed_local(self, synthetic):
        s = synthetic
        f = train_als(ComputeContext.local(), s["u"], s["i"], s["r"], s["U"], s["I"], CFG)
        pred = f.user_factors @ f.item_factors.T
        rmse = np.sqrt(np.mean((pred[s["u"], s["i"]] - s["r"]) ** 2))
        assert rmse < 0.05
        assert f.user_factors.shape == (s["U"], 8)
        assert f.item_factors.shape == (s["I"], 8)

    def test_mesh_matches_local(self, synthetic):
        s = synthetic
        f_local = train_als(
            ComputeContext.local(), s["u"], s["i"], s["r"], s["U"], s["I"], CFG
        )
        f_mesh = train_als(
            ComputeContext.create(), s["u"], s["i"], s["r"], s["U"], s["I"], CFG
        )
        pl = f_local.user_factors @ f_local.item_factors.T
        pm = f_mesh.user_factors @ f_mesh.item_factors.T
        # same predictions up to reduction-order float noise
        assert np.abs(pl - pm).max() < 0.05

    @pytest.mark.parametrize("implicit", [False, True],
                             ids=["explicit", "implicit"])
    def test_mesh_compact_wire_matches_blocked(self, synthetic, implicit):
        """The mesh route (sharded h2d → ICI all-gather → device
        dual-layout construction) must train BYTE-IDENTICAL factors to
        its oracle, the host-packed blocks shipped as they are: the two
        feed the same shard_map trainer and device_pack is bit-identical
        to the host packer."""
        s = synthetic
        rng = np.random.default_rng(5)
        r_grid = (rng.integers(1, 11, len(s["u"])) * 0.5).astype(np.float32)
        cfg = ALSConfig(rank=8, iterations=12, reg=0.01, implicit=implicit,
                        alpha=2.0, blocks_per_chunk=64)
        ctx = ComputeContext.create()
        f_blocked = _train_mesh_host_packed(
            ctx, s["u"], s["i"], r_grid, s["U"], s["I"], cfg)
        st_c = {}
        f_compact = train_als(
            ctx, s["u"], s["i"], r_grid, s["U"], s["I"], cfg, stats=st_c)
        assert np.isfinite(f_blocked.user_factors).all()
        assert np.array_equal(
            f_blocked.user_factors, f_compact.user_factors
        )
        assert np.array_equal(
            f_blocked.item_factors, f_compact.item_factors
        )
        # the edges cross the host link once, 8 B each, beside the two
        # degree histograms padded to the mesh
        n_dev = ctx.mesh.shape[ctx.batch_axis]
        pads = -(-s["U"] // n_dev) * n_dev + -(-s["I"] // n_dev) * n_dev
        assert st_c["wire_bytes"] == 8 * len(s["u"]) + 4 * pads, st_c

    def test_mesh_compact_wire_chunked_stream(self, synthetic,
                                              monkeypatch):
        """PIO_TPU_ALS_STREAM_MB applies to the mesh path too: the
        edges ship as multiple sharded spans (pipelined puts) and the
        trainer splices them back: factors stay byte-identical to the
        oracle and the stats record the per-chunk timings."""
        s = synthetic
        rng = np.random.default_rng(7)
        r_grid = (rng.integers(1, 11, len(s["u"])) * 0.5).astype(np.float32)

        f_blocked = _train_mesh_host_packed(
            ComputeContext.create(), s["u"], s["i"], r_grid,
            s["U"], s["I"], CFG,
        )
        monkeypatch.setenv("PIO_TPU_ALS_STREAM_MB", "0.001")  # force chunks
        st = {}
        f_chunked = train_als(
            ComputeContext.create(), s["u"], s["i"], r_grid,
            s["U"], s["I"], CFG, stats=st,
        )
        assert st["n_stream"] > 1, st
        assert len(st["h2d_chunk_s"]) == st["n_stream"], st
        assert np.array_equal(
            f_blocked.user_factors, f_chunked.user_factors
        )
        assert np.array_equal(
            f_blocked.item_factors, f_chunked.item_factors
        )

    def test_mesh_compact_item_ids_over_2_16(self):
        """Item ids at and over 2^16 ride the sharded put + slice path
        as what they are and must stay byte-identical to the oracle."""
        rng = np.random.default_rng(11)
        n = 3000
        u = rng.integers(0, 40, n).astype(np.int32)
        i = rng.integers(0, 70_000, n).astype(np.int32)
        i[:3] = (65_535, 65_536, 69_999)
        r = (rng.integers(1, 11, n) * 0.5).astype(np.float32)
        cfg = ALSConfig(rank=4, iterations=4, reg=0.05,
                        blocks_per_chunk=16)
        f_b = _train_mesh_host_packed(
            ComputeContext.create(), u, i, r, 40, 70_000, cfg)
        f_c = train_als(ComputeContext.create(), u, i, r, 40, 70_000, cfg)
        assert np.array_equal(f_b.user_factors, f_c.user_factors)
        assert np.array_equal(f_b.item_factors, f_c.item_factors)

    def test_mesh_compact_sparse_adjacencies(self):
        """A handful of items a user, spread over the whole item range
        (within-user gaps over 4095); factors must match the oracle
        exactly."""
        rng = np.random.default_rng(12)
        n_users, n_items = 24, 60_000
        us, its = [], []
        for uu in range(n_users):
            for ii in range(0, n_items, 7013):
                us.append(uu)
                its.append((ii + uu * 311) % n_items)
        u = np.array(us, np.int32)
        i = np.array(its, np.int32)
        r = (rng.integers(1, 11, len(u)) * 0.5).astype(np.float32)
        cfg = ALSConfig(rank=4, iterations=3, reg=0.05,
                        blocks_per_chunk=16)
        f_b = _train_mesh_host_packed(
            ComputeContext.create(), u, i, r, n_users, n_items, cfg)
        f_c = train_als(ComputeContext.create(), u, i, r,
                        n_users, n_items, cfg)
        assert np.array_equal(f_b.user_factors, f_c.user_factors)
        assert np.array_equal(f_b.item_factors, f_c.item_factors)

    def test_implicit_separates_observed(self, synthetic):
        s = synthetic
        f = train_als(
            ComputeContext.create(),
            s["u"], s["i"], np.abs(s["r"]), s["U"], s["I"],
            ALSConfig(rank=8, iterations=8, reg=0.1, implicit=True, alpha=10,
                      blocks_per_chunk=64),
        )
        pred = f.user_factors @ f.item_factors.T
        hu, hi = np.nonzero(~s["mask"])
        assert pred[s["u"], s["i"]].mean() > pred[hu, hi].mean() + 0.1

    def test_cg_solver_matches_cholesky(self, synthetic):
        """The >32k-entity perf path (CG) must agree with the exact solver
        on the observed entries (well-conditioned config: rank ≤ data
        rank, real regularization)."""
        s = synthetic
        cfg = dict(rank=4, iterations=10, reg=0.1, blocks_per_chunk=64)
        preds = {}
        for solver in ("cholesky", "cg"):
            f = train_als(
                ComputeContext.local(), s["u"], s["i"], s["r"],
                s["U"], s["I"], ALSConfig(solver=solver, **cfg),
            )
            preds[solver] = (f.user_factors @ f.item_factors.T)[
                s["u"], s["i"]
            ]
        err = np.abs(preds["cg"] - preds["cholesky"]).max()
        assert err < 0.05, err

    def test_unknown_solver_raises(self, synthetic):
        s = synthetic
        with pytest.raises(Exception, match="unknown ALS solver"):
            train_als(
                ComputeContext.local(), s["u"], s["i"], s["r"],
                s["U"], s["I"], ALSConfig(solver="choleski"),
            )

    def test_empty_ratings_raises(self):
        with pytest.raises(ValueError, match="at least one rating"):
            train_als(
                ComputeContext.local(),
                np.array([], np.int32), np.array([], np.int32),
                np.array([], np.float32), 5, 5,
            )

    def test_native_sort_by_entity_matches_numpy(self):
        """C++ counting sort (what makes the user column one repeat of
        the counts) must match numpy's stable argsort exactly."""
        from pio_tpu.models.als import (
            _f32p, _i32p, _i64p, _native_packer,
        )

        native = _native_packer()
        if native is None:
            pytest.skip("no native toolchain")
        rng = np.random.default_rng(5)
        E, N = 40_000, 321
        ent = rng.integers(0, N, E).astype(np.int32)
        other = rng.integers(0, 7777, E).astype(np.int32)
        rat = rng.random(E).astype(np.float32)
        counts = np.zeros(N, np.int64)
        native.als_pack_count(_i32p(ent), E, N, 16, _i64p(counts))
        o_sorted = np.empty(E, np.int32)
        r_sorted = np.empty(E, np.float32)
        native.als_sort_by_entity(
            _i32p(ent), _i32p(other), _f32p(rat), E, N, _i64p(counts),
            _i32p(o_sorted), _f32p(r_sorted),
        )
        order = np.argsort(ent, kind="stable")
        assert (o_sorted == other[order]).all()
        assert (r_sorted == rat[order]).all()

    def test_native_and_numpy_paths_agree_bitwise(self, synthetic,
                                                  monkeypatch):
        """Single-device training must not depend on which host sorter
        ran (same stable edge order → same floats)."""
        s = synthetic
        f1 = train_als(
            ComputeContext.local(), s["u"], s["i"], s["r"], s["U"], s["I"],
            CFG,
        )
        monkeypatch.setenv("PIO_TPU_NO_NATIVE", "1")
        f2 = train_als(
            ComputeContext.local(), s["u"], s["i"], s["r"], s["U"], s["I"],
            CFG,
        )
        assert (f1.user_factors == f2.user_factors).all()
        assert (f1.item_factors == f2.item_factors).all()

    def test_non_grid_ratings_train(self):
        """Ratings off every grid (not fp16-exact) train as they are."""
        rng = np.random.default_rng(3)
        E = 400
        u = rng.integers(0, 30, E).astype(np.int32)
        i = rng.integers(0, 20, E).astype(np.int32)
        r = (rng.random(E) * 3.7 + 0.123).astype(np.float32)  # not fp16-exact
        f = train_als(ComputeContext.local(), u, i, r, 30, 20,
                      ALSConfig(rank=4, iterations=3, reg=0.05))
        assert np.isfinite(f.user_factors).all()
        pred = (f.user_factors[u] * f.item_factors[i]).sum(1)
        assert np.sqrt(np.mean((pred - r) ** 2)) < 1.0

    @pytest.mark.parametrize("form", _PACK_FORMS)
    @pytest.mark.parametrize("counts_of,width", _PACK_LAYOUTS)
    def test_device_pack_matches_host_packers(self, counts_of, width, form):
        """The on-device packer must be bit-identical to the host layout
        (the trainer's correctness rides on ascending block_ent for
        indices_are_sorted segment sums and -1 padding sentinels), for
        every way the trainers call it: ``shuffled`` (edges in any order
        with ties, the stable order decides the layout; counted on the
        device), ``shuffled_counts`` (``finalize``'s item side),
        ``sorted`` (``run_packed``'s user side) and ``chunk`` (a streamed
        chunk: no ``ent``, the padding blocks at the last present
        entity)."""
        import jax
        import jax.numpy as jnp

        from pio_tpu.models.als import _pack_blocks, device_pack

        rng = np.random.default_rng(21)
        counts = np.asarray(counts_of(rng), np.int64)
        N, E = len(counts), int(counts.sum())
        ent = np.repeat(np.arange(N, dtype=np.int32), counts)
        if form.startswith("shuffled"):
            ent = rng.permutation(ent)
        # every edge its own id and rating: a slot names the edge it holds
        oth = rng.permutation(E).astype(np.int32)
        rat = (rng.random(E) + 0.5).astype(np.float32)
        ref_ent, ref_oth, ref_rat = _pack_blocks(ent, oth, rat, N, width, 8)
        S = ref_ent.shape[0]
        kw = {"assume_sorted": form in ("sorted", "chunk")}
        if form == "chunk":
            kw["pad_entity"] = last = int(np.flatnonzero(counts)[-1])
            ref_ent = ref_ent.copy()
            ref_ent[int((-(-counts // width)).sum()):] = last
        got = jax.jit(
            lambda e, o, r, c: device_pack(e, o, r, N, width, S, counts=c,
                                           **kw)
        )(None if form == "chunk" else jnp.asarray(ent),
          jnp.asarray(oth), jnp.asarray(rat),
          None if form == "shuffled" else jnp.asarray(counts, jnp.int32))
        assert (np.asarray(got[0]) == ref_ent).all()
        assert (np.asarray(got[1]) == ref_oth).all()
        assert (np.asarray(got[2]) == ref_rat).all()

    @pytest.mark.parametrize("counts", [
        [3, 1, 4], [0, 0, 3, 0, 2, 0, 0], [5], [0, 1], [1, 0],
        list(range(40)),
    ], ids=["dense", "empty-front-middle-end", "one-entity", "empty-first",
            "empty-last", "ramp"])
    def test_entity_column_is_repeat_of_the_counts(self, counts):
        """The per-edge entity ids the item side's pack is handed are
        ``repeat(arange(n), counts)``, empty entities wherever they
        stand."""
        import jax
        import jax.numpy as jnp

        from pio_tpu.models.als import _entity_column

        got = jax.jit(_entity_column, static_argnums=1)(
            jnp.asarray(counts, jnp.int32), sum(counts))
        assert (np.asarray(got)
                == np.repeat(np.arange(len(counts)), counts)).all()

    @pytest.mark.parametrize("side", ["user", "item"])
    @pytest.mark.parametrize("route", ["monolithic", "streamed", "mesh"])
    def test_wide_id_space(self, route, side, monkeypatch):
        """Entity ids at and over 2^16 on either side train the rows they
        name on every route; a mis-widened id would train the wrong
        rows."""
        rng = np.random.default_rng(5)
        wide = [65_536, 70_000, 99_999]  # beyond the uint16 range
        a = np.array(wide * 40, np.int32)
        b = rng.integers(0, 8, len(a)).astype(np.int32)
        R = rng.normal(size=(3, 8)).astype(np.float32)
        r = np.array(
            [R[wide.index(aa), bb] for aa, bb in zip(a, b)], np.float32
        )
        u, i, U, I = (a, b, 100_000, 8) if side == "user" \
            else (b, a, 8, 100_000)
        if route == "streamed":
            monkeypatch.setenv("PIO_TPU_ALS_STREAM_MB", "0.0002")
        ctx = (ComputeContext.create() if route == "mesh"
               else ComputeContext.local())
        st = {}
        f = train_als(ctx, u, i, r, U, I,
                      ALSConfig(rank=4, iterations=10, reg=0.05), stats=st)
        assert (st["n_stream"] > 1) == (route == "streamed"), st
        pred = (f.user_factors[u] * f.item_factors[i]).sum(1)
        rmse = float(np.sqrt(np.mean((pred - r) ** 2)))
        assert rmse < 0.1, rmse
        # untouched rows: zero on the side solved last, and never more
        # than the tiny init scale
        wide_table = f.user_factors if side == "user" else f.item_factors
        assert np.abs(wide_table[500]).max() < 0.05
        assert np.abs(wide_table[65_535]).max() < 0.05

    def test_numpy_fallback_trains(self, synthetic, monkeypatch):
        monkeypatch.setenv("PIO_TPU_NO_NATIVE", "1")
        s = synthetic
        f = train_als(
            ComputeContext.local(), s["u"], s["i"], s["r"], s["U"], s["I"],
            CFG,
        )
        pred = f.user_factors @ f.item_factors.T
        rmse = np.sqrt(np.mean((pred[s["u"], s["i"]] - s["r"]) ** 2))
        assert rmse < 0.05

    def test_single_rating(self):
        f = train_als(
            ComputeContext.create(),
            np.array([0], np.int32), np.array([0], np.int32),
            np.array([5.0], np.float32), 1, 1,
            ALSConfig(rank=2, iterations=3, reg=0.01),
        )
        pred = float(f.user_factors[0] @ f.item_factors[0])
        assert abs(pred - 5.0) < 0.5

    @pytest.mark.parametrize("ratings", ["real", "halfstar"])
    def test_streamed_matches_monolithic(self, synthetic, monkeypatch,
                                         ratings):
        """The double-buffered chunked shipment must train the same model
        as the single-dispatch path (it differs only in iteration-1
        accumulation grouping — float reduction order), on real-valued
        ratings and on the half-star grid."""
        s = synthetic
        r = s["r"]
        if ratings == "halfstar":
            rng = np.random.default_rng(9)
            r = (rng.integers(1, 11, len(s["u"])) * 0.5).astype(np.float32)
        stats = {}
        f_mono = train_als(
            ComputeContext.local(), s["u"], s["i"], r, s["U"], s["I"],
            CFG, stats=stats,
        )
        assert stats["n_stream"] == 1, stats
        # ~KB-scale threshold forces the max 8 stream chunks on this data
        monkeypatch.setenv("PIO_TPU_ALS_STREAM_MB", "0.0005")
        stats = {}
        f_str = train_als(
            ComputeContext.local(), s["u"], s["i"], r, s["U"], s["I"],
            CFG, stats=stats,
        )
        assert stats["n_stream"] > 1, stats
        pm = f_mono.user_factors @ f_mono.item_factors.T
        ps = f_str.user_factors @ f_str.item_factors.T
        assert np.abs(pm - ps).max() < 0.05

    def test_stream_disable_env(self, synthetic, monkeypatch):
        """PIO_TPU_ALS_STREAM_MB <= 0 means 'streaming off' — the
        intuitive disable value must not degenerate into a 1-byte
        threshold that forces the max chunked path."""
        s = synthetic
        monkeypatch.setenv("PIO_TPU_ALS_STREAM_MB", "0")
        stats = {}
        train_als(
            ComputeContext.local(), s["u"], s["i"], s["r"], s["U"], s["I"],
            CFG, stats=stats,
        )
        assert stats["n_stream"] == 1, stats

    def test_streamed_chunk_carry(self, monkeypatch):
        """Sparse adjacencies over a wide item space, cut into chunks
        whose bounds split users mid-adjacency (such a user is in both
        chunks' local counts with its share of each): streamed must
        still train what the monolithic path trains."""
        from pio_tpu.models.als import _edge_spans

        rng = np.random.default_rng(17)
        U, I, E = 25, 50_000, 1_200
        u = np.sort(rng.integers(0, U, E)).astype(np.int32)
        i = rng.integers(0, I, E).astype(np.int32)
        r = (rng.integers(1, 11, E) * 0.5).astype(np.float32)
        # sanity: the cuts really fall inside adjacencies
        starts = np.concatenate([[0], np.cumsum(np.bincount(u, minlength=U))])
        cuts = [e0 for e0, _ in _edge_spans(E, 8)][1:]
        assert len(cuts) == 7 and not set(cuts) & set(starts.tolist()), cuts

        cfg = ALSConfig(rank=4, iterations=5, reg=0.1, blocks_per_chunk=16)
        f_mono = train_als(ComputeContext.local(), u, i, r, U, I, cfg)
        monkeypatch.setenv("PIO_TPU_ALS_STREAM_MB", "0.0002")  # many chunks
        st = {}
        f_str = train_als(ComputeContext.local(), u, i, r, U, I, cfg,
                          stats=st)
        assert st["n_stream"] == 8, st
        # the same sums in another grouping of iteration 1: rounding alone
        for got, want in ((f_str.user_factors, f_mono.user_factors),
                          (f_str.item_factors, f_mono.item_factors)):
            assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()

    def test_chunk_rule(self):
        """The streamed feed's chunk count and cuts as a function of the
        edge count alone: MovieLens-25M's 25,000,095 edges go in 7 chunks
        on the cuts the benchmark's programs were compiled for, and what
        fits one chunk of 30 MiB is not streamed."""
        from pio_tpu.models.als import _EDGE_BYTES, _edge_spans
        from pio_tpu.parallel.stream import n_stream_chunks

        def chunks(n_edges):
            return n_stream_chunks(_EDGE_BYTES * n_edges,
                                   "PIO_TPU_ALS_STREAM_MB")

        E = 25_000_095
        assert chunks(E) == 7
        assert chunks(3_900_000) == 1
        assert chunks(10 ** 9) == 8  # the cap
        cuts = [0, 3_571_442, 7_142_884, 10_714_326, 14_285_768,
                17_857_210, 21_428_652, E]
        assert _edge_spans(E, 7) == list(zip(cuts[:-1], cuts[1:]))
        # more chunks than edges: the empty spans are dropped
        assert _edge_spans(3, 8) == [(0, 2), (2, 3)]

    @pytest.mark.parametrize("route", ["monolithic", "streamed", "mesh"])
    def test_rating_values_pick_no_program(self, synthetic, route,
                                           fresh_trainers, monkeypatch):
        """Half-star and then off-grid ratings of one graph run the same
        compiled programs: the data's values are no static argument."""
        s = synthetic
        rng = np.random.default_rng(9)
        halfstar = (rng.integers(1, 11, len(s["u"])) * 0.5).astype(np.float32)
        offgrid = (rng.random(len(s["u"])) * 3.7 + 0.123).astype(np.float32)
        seen = _watch_programs(monkeypatch)
        if route == "streamed":
            monkeypatch.setenv("PIO_TPU_ALS_STREAM_MB", "0.0005")
        ctx = (ComputeContext.create() if route == "mesh"
               else ComputeContext.local())
        train_als(ctx, s["u"], s["i"], halfstar, s["U"], s["I"], CFG)
        first = seen()
        assert first["programs"] > 0, first
        train_als(ctx, s["u"], s["i"], offgrid, s["U"], s["I"], CFG)
        assert seen() == first

    @pytest.mark.parametrize("route", ["monolithic", "streamed"])
    def test_ids_pick_no_program(self, route, fresh_trainers, monkeypatch):
        """Two graphs with the same two degree sequences and different
        ids (pairs of edges exchange their items) share every compiled
        program: no compiled shape depends on the ids."""
        rng = np.random.default_rng(23)
        U, I, E = 25, 50_000, 1_200
        u = np.sort(rng.integers(0, U, E)).astype(np.int32)
        i = rng.integers(0, I, E).astype(np.int32)
        r = (rng.integers(1, 11, E) * 0.5).astype(np.float32)
        i2 = i.copy()
        pairs = rng.permutation(E).reshape(-1, 2)
        i2[pairs[:, 0]], i2[pairs[:, 1]] = i[pairs[:, 1]], i[pairs[:, 0]]
        assert (i2 != i).mean() > 0.9
        assert (np.bincount(i2, minlength=I) == np.bincount(i, minlength=I)).all()

        cfg = ALSConfig(rank=4, iterations=3, reg=0.1, blocks_per_chunk=16)
        seen = _watch_programs(monkeypatch)
        if route == "streamed":
            monkeypatch.setenv("PIO_TPU_ALS_STREAM_MB", "0.0002")
        train_als(ComputeContext.local(), u, i, r, U, I, cfg)
        first = seen()
        assert first["programs"] > 0, first
        train_als(ComputeContext.local(), u, i2, r, U, I, cfg)
        assert seen() == first

    def test_native_within_entity_sort_matches_lexsort(self):
        """The native (user, item) two-pass sort must equal numpy's
        lexsort order exactly (stability on duplicate pairs included)."""
        from pio_tpu.models.als import (
            _f32p, _i32p, _i64p, _native_packer,
        )

        native = _native_packer()
        if native is None:
            pytest.skip("no native toolchain")
        rng = np.random.default_rng(8)
        E, U, I = 30_000, 200, 500
        u = rng.integers(0, U, E).astype(np.int32)
        i = rng.integers(0, I, E).astype(np.int32)  # many duplicates
        r = rng.random(E).astype(np.float32)
        counts = np.zeros(U, np.int64)
        native.als_pack_count(_i32p(u), E, U, 16, _i64p(counts))
        i_s = np.empty(E, np.int32)
        r_s = np.empty(E, np.float32)
        native.als_sort_by_entity(
            _i32p(u), _i32p(i), _f32p(r), E, U, _i64p(counts),
            _i32p(i_s), _f32p(r_s),
        )
        native.als_sort_within_entity(
            _i32p(i_s), _f32p(r_s), U, _i64p(counts)
        )
        order = np.lexsort((i, u))
        assert (i_s == i[order]).all()
        assert (r_s == r[order]).all()

    def test_stats_phases(self, synthetic):
        """Profiling mode fills the per-phase breakdown on every path."""
        s = synthetic
        for ctx, n_dev in ((ComputeContext.local(), 1),
                           (ComputeContext.create(), 8)):
            st = {}
            train_als(ctx, s["u"], s["i"], s["r"], s["U"], s["I"], CFG,
                      stats=st)
            for k in ("pack_s", "wire_bytes", "h2d_s", "device_s",
                      "n_stream"):
                assert k in st, (k, st)
            assert "encoding" not in st
            # 8 B an edge and the two degree histograms, whatever the data
            pads = -(-s["U"] // n_dev) * n_dev + -(-s["I"] // n_dev) * n_dev
            assert st["wire_bytes"] == 8 * len(s["u"]) + 4 * pads, st
            assert st["device_s"] > 0

    def test_entity_counts_not_multiple_of_mesh(self, synthetic):
        # 7 users, 3 items on an 8-device mesh exercises entity padding
        u = np.array([0, 1, 2, 3, 4, 5, 6, 0, 1], np.int32)
        i = np.array([0, 1, 2, 0, 1, 2, 0, 2, 0], np.int32)
        r = np.ones(9, np.float32) * 2.0
        f = train_als(ComputeContext.create(), u, i, r, 7, 3,
                      ALSConfig(rank=2, iterations=4, reg=0.01))
        assert f.user_factors.shape == (7, 2)
        assert f.item_factors.shape == (3, 2)
        assert np.isfinite(f.user_factors).all()


def _spd_batch(n, K, seed=0):
    """Random SPD systems, well enough conditioned that K+8 CG sweeps
    converge to float32 rounding whatever the summation order."""
    rng = np.random.default_rng(seed)
    W = rng.standard_normal((n, K, 2 * K)).astype(np.float32)
    A = np.einsum("nkw,nlw->nkl", W, W).astype(np.float32)
    b = rng.standard_normal((n, K)).astype(np.float32)
    G = rng.standard_normal((K, 3 * K)).astype(np.float32)
    return A, b, (G @ G.T).astype(np.float32)


def _both_cg(A, b, gram, implicit, reg=0.1):
    """(the XLA loop through ``solve_block`` as the CPU selects it, the
    Pallas kernel interpreted) on one batch."""
    import jax
    import jax.numpy as jnp

    from pio_tpu.models import als

    math = als._make_math(reg, implicit, 1.0, "float32", "cg")
    want = np.asarray(jax.jit(math.solve_block)(A, b, gram))
    reg_kk = reg * jnp.eye(A.shape[1], dtype=jnp.float32)
    if implicit:
        reg_kk = reg_kk + gram
    got = np.asarray(jax.jit(
        lambda A, b, r: als._cg_solve_resident(A, b, r, interpret=True)
    )(A, b, reg_kk))
    return want, got


def _watch_programs(monkeypatch):
    """``seen()`` → how many trainers were built (the builders' cache
    misses) and how many programs their jitted functions hold compiled,
    over every trainer ``train_als`` is handed from now on."""
    import jax

    from pio_tpu.models import als

    builders = [als._build_trainer, als._build_stream_trainer]
    built = []
    for builder in builders:
        def watched(*args, _builder=builder, **kwargs):
            out = _builder(*args, **kwargs)
            if not any(out is b for b in built):
                built.append(out)
            return out

        watched.cache_clear = builder.cache_clear
        monkeypatch.setattr(als, builder.__name__, watched)

    def seen():
        return {
            "trainers": sum(b.cache_info().misses for b in builders),
            "programs": sum(
                j._cache_size() for j in jax.tree_util.tree_leaves(built)),
        }

    return seen


@pytest.fixture
def fresh_trainers():
    """The trainers are cached per static config and read the selection
    rule when traced; a test that steers the rule empties the caches
    (``clear``) after it changes it, and leaves them empty."""
    from pio_tpu.models import als

    def clear():
        als._build_trainer.cache_clear()
        als._build_stream_trainer.cache_clear()

    clear()
    yield clear
    clear()


class TestResidentCG:
    """The VMEM-resident CG kernel (``_cg_solve_resident``) against the
    XLA loop it replaces on a TPU (``_cg_solve``, the oracle), and the
    rule that chooses between them."""

    @pytest.mark.parametrize("implicit", [False, True],
                             ids=["explicit", "implicit"])
    @pytest.mark.parametrize("K,n", [(16, 300), (64, 200)])
    def test_kernel_matches_xla_loop(self, K, n, implicit):
        # n is no multiple of the 128-entity tile: the last tile is partial
        A, b, gram = _spd_batch(n, K)
        want, got = _both_cg(A, b, gram, implicit)
        assert got.shape == want.shape == (n, K)
        scale = np.abs(want).max()
        assert np.abs(got - want).max() <= 2e-6 * scale, (
            np.abs(got - want).max(), scale)

    def test_as_accurate_as_the_xla_loop_on_ill_conditioned_systems(self):
        """ALS's own hard case: degree about the rank, correlated positive
        bf16 rows, condition numbers in the thousands. There CG multiplies
        the matvec's rounding, so the order of its 64 additions shows: one
        after another they cost 2.6x the XLA reduction's error against a
        float64 solve (and read +4% on the chip cell's ``item_factors.fro``);
        the kernel's runs-of-8 tree must stay level with XLA."""
        import jax.numpy as jnp

        rng = np.random.default_rng(0)
        K, n, deg = 64, 256, 96
        Q = (np.abs(rng.standard_normal((4000, K))) / 8
             + 0.3 * np.abs(rng.standard_normal((4000, 1))))
        Q = np.asarray(jnp.asarray(Q, jnp.bfloat16).astype(jnp.float32))
        q = Q[rng.integers(0, 4000, (n, deg))]  # [n, deg, K]
        r = (rng.integers(1, 11, (n, deg)) * 0.5).astype(np.float32)
        A = np.einsum("ndk,ndl->nkl", q, q).astype(np.float32)
        b = np.einsum("ndk,nd->nk", q, r).astype(np.float32)
        exact = np.linalg.solve(
            A.astype(np.float64) + 0.1 * np.eye(K),
            b.astype(np.float64)[:, :, None])[:, :, 0]
        want, got = _both_cg(A, b, np.zeros((K, K), np.float32), False)
        err_xla = np.linalg.norm(want - exact) / np.linalg.norm(exact)
        err_res = np.linalg.norm(got - exact) / np.linalg.norm(exact)
        assert 1e-5 < err_xla < 1e-2, err_xla  # hard, and still solved
        assert err_res <= 1.25 * err_xla, (err_res, err_xla)

    def test_zero_rhs_and_padding_row_are_finite(self):
        """b = 0 makes every CG denominator 0; an entity with no
        observation has A = 0, so its system is the regulariser alone.
        Both must come out as finite zeros, and leave their neighbours'
        solutions alone."""
        A, b, gram = _spd_batch(130, 16, seed=1)
        A0, b0 = A.copy(), b.copy()
        b0[3] = 0.0
        A0[5], b0[5] = 0.0, 0.0
        want, got = _both_cg(A0, b0, gram, implicit=False)
        assert np.isfinite(got).all()
        assert (got[3] == 0).all() and (got[5] == 0).all()
        _, clean = _both_cg(A, b, gram, implicit=False)
        keep = np.ones(130, bool)
        keep[[3, 5]] = False
        assert np.array_equal(got[keep], clean[keep])
        assert np.abs(got - want).max() <= 2e-6 * np.abs(want).max()

    @pytest.mark.parametrize("solver,n,rank,platform,want", [
        ("auto", 162541, 64, "tpu", "resident_cg"),
        ("cg", 100, 16, "tpu", "resident_cg"),
        ("auto", 162541, 64, "cpu", "xla_cg"),
        ("cg", 162541, 64, "gpu", "xla_cg"),
        ("auto", 162541, 10, "tpu", "xla_cg"),
        ("auto", 40000, 128, "tpu", "resident_cg"),
        ("auto", 40000, 256, "tpu", "xla_cg"),
        ("auto", 32768, 64, "tpu", "cholesky"),
        ("auto", 32769, 64, "tpu", "resident_cg"),
        ("cholesky", 162541, 64, "tpu", "cholesky"),
        ("lu", 162541, 64, "tpu", "lu"),
    ])
    def test_selection_rule(self, solver, n, rank, platform, want):
        from pio_tpu.models.als import _solve_impl

        assert _solve_impl(solver, n, rank, platform) == want

    @pytest.mark.parametrize("solver,want", [
        ("auto", "cholesky"), ("cg", "xla_cg"), ("lu", "lu")])
    def test_stats_name_the_cpu_paths(self, synthetic, solver, want):
        """On CPU the kernel is never picked; ``stats`` and the run
        record say which solver ran, per side."""
        from pio_tpu.obs import trainwatch

        s = synthetic
        st = {}
        recorder = trainwatch.StepRecorder("solve-impl")
        with trainwatch.recording(recorder):
            train_als(
                ComputeContext.local(), s["u"], s["i"], s["r"], s["U"],
                s["I"], ALSConfig(rank=8, iterations=2, solver=solver,
                                  blocks_per_chunk=64), stats=st)
        assert st["solve_impl"] == {"user": want, "item": want}
        record = trainwatch.run_record(
            run_id="r", engine_id="e", status="COMPLETED",
            train_seconds=1.0, phases={}, params_hash="h",
            step_summary=recorder.summary())
        assert record["solve_impl"] == {"user": want, "item": want}

    @pytest.mark.parametrize("path", ["monolithic", "streamed", "mesh"])
    def test_train_als_on_the_interpreted_kernel(self, synthetic, path,
                                                 fresh_trainers,
                                                 monkeypatch):
        """End to end through each trainer that calls ``solve_block``:
        the kernel (interpreted) trains the model the XLA loop trains."""
        from pio_tpu.models import als

        s = synthetic
        cfg = ALSConfig(rank=8, iterations=6, reg=0.05, solver="cg",
                        blocks_per_chunk=64)
        if path == "streamed":
            monkeypatch.setenv("PIO_TPU_ALS_STREAM_MB", "0.0005")
        ctx = (ComputeContext.create() if path == "mesh"
               else ComputeContext.local())

        def train(want):
            st = {}
            f = train_als(ctx, s["u"], s["i"], s["r"], s["U"], s["I"], cfg,
                          stats=st)
            assert st["solve_impl"] == {"user": want, "item": want}
            assert (st["n_stream"] > 1) == (path == "streamed")
            return f.user_factors @ f.item_factors.T

        want = train("xla_cg")  # the real rule: on CPU, the XLA loop
        # the rule as a TPU would read it; off a TPU solve_block then
        # interprets the kernel
        rule = als._solve_impl
        monkeypatch.setattr(
            als, "_solve_impl",
            lambda solver, n, rank, platform: rule(solver, n, rank, "tpu"))
        fresh_trainers()
        got = train("resident_cg")
        # rank 8 on rank-4 data leaves the factors a rotation's freedom
        # that rounding picks; the predictions are what is determined
        assert np.abs(got - want).max() <= 1e-3 * np.abs(want).max()


class TestPackedGather:
    """The lane-dense factor table (``_pack_table`` / ``_gather_rows``)
    against the plain gather it replaces on a TPU (the oracle), and the
    rule that chooses between them (``_gather_impl``)."""

    @pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
    @pytest.mark.parametrize("K", [8, 16, 64])
    def test_packed_rows_equal_plain_to_the_bit(self, K, dtype):
        """1,001 rows are no multiple of 16, 8 or 2 entities a lane row;
        a chunk's padded slots (``other == -1``) read row 0 in both."""
        import jax
        import jax.numpy as jnp

        from pio_tpu.models.als import _LANES, _gather_rows, _pack_table

        rng = np.random.default_rng(K)
        n = 1001
        table = jnp.asarray(rng.standard_normal((n, K)), jnp.dtype(dtype))
        other = rng.integers(0, n, (32, 16)).astype(np.int32)
        other[rng.random(other.shape) < 0.2] = -1
        other[0, :3] = (n - 1, n - 2, 0)  # the padded last lane row
        idx = jnp.maximum(other, 0)
        packed = _pack_table(table)
        assert packed.shape == (-(-n // (_LANES // K)), _LANES)
        want = jax.jit(lambda t, i: _gather_rows(t, i, K))(table, idx)
        got = jax.jit(lambda t, i: _gather_rows(t, i, K))(packed, idx)
        assert got.shape == want.shape == (32, 16, K)
        assert got.dtype == want.dtype
        as_bits = lambda a: np.asarray(a.astype(jnp.float32)).view(np.uint32)
        assert np.array_equal(as_bits(got), as_bits(table[idx]))
        assert np.array_equal(as_bits(got), as_bits(want))

    @pytest.mark.parametrize("platform,n_rows,rank,itemsize,want", [
        # the benchmark cell: the user table's 41.6 MB as tiled do not
        # fit beside a chunk's rows, its 20.8 MB packed do; the item
        # table's 15.1 MB fit as they are, and packing costs a select
        ("tpu", 162_541, 64, 2, "packed"),
        ("tpu", 59_047, 64, 2, "plain"),
        ("cpu", 162_541, 64, 2, "plain"),
        ("gpu", 162_541, 64, 2, "plain"),
        ("tpu", 162_541, 10, 2, "plain"),   # 128 % 10 != 0: implicit
        ("tpu", 162_541, 48, 2, "plain"),
        ("tpu", 162_541, 128, 2, "plain"),  # a row fills the lanes
        ("tpu", 162_541, 256, 2, "plain"),
        # at the threshold: 24 MiB as tiled is 98,304 bf16 rows
        ("tpu", 98_304, 64, 2, "plain"),
        ("tpu", 98_305, 64, 2, "packed"),
        ("tpu", 196_608, 64, 2, "packed"),
        ("tpu", 196_609, 64, 2, "plain"),   # packed does not fit either
        ("tpu", 59_047, 64, 4, "packed"),   # float32 rows are twice as wide
        ("tpu", 40_000, 16, 2, "plain"),    # the quickstart
        ("tpu", 162_541, 16, 2, "packed"),  # eight entities a lane row
        ("tpu", 800_000, 16, 2, "plain"),
    ])
    def test_selection_rule(self, platform, n_rows, rank, itemsize, want):
        from pio_tpu.models.als import _gather_impl

        assert _gather_impl(platform, n_rows, rank, itemsize) == want

    def test_stats_and_run_record_name_the_layout(self, synthetic,
                                                  fresh_trainers,
                                                  monkeypatch):
        """``plain`` on CPU by the rule, ``packed`` once steered; ``stats``
        and the run record carry it per half-step."""
        from pio_tpu.models import als
        from pio_tpu.obs import trainwatch

        s = synthetic

        def train():
            st = {}
            recorder = trainwatch.StepRecorder("gather-impl")
            with trainwatch.recording(recorder):
                train_als(
                    ComputeContext.local(), s["u"], s["i"], s["r"], s["U"],
                    s["I"], ALSConfig(rank=8, iterations=2,
                                      blocks_per_chunk=64), stats=st)
            record = trainwatch.run_record(
                run_id="r", engine_id="e", status="COMPLETED",
                train_seconds=1.0, phases={}, params_hash="h",
                step_summary=recorder.summary())
            assert record["gather_impl"] == st["gather_impl"]
            return st["gather_impl"]

        assert train() == {"user": "plain", "item": "plain"}
        _steer_packed(monkeypatch, als)
        fresh_trainers()
        assert train() == {"user": "packed", "item": "packed"}

    @pytest.mark.parametrize("implicit", [False, True],
                             ids=["explicit", "implicit"])
    @pytest.mark.parametrize("path", ["monolithic", "streamed", "mesh"])
    def test_train_als_on_the_packed_table(self, synthetic, path, implicit,
                                           fresh_trainers, monkeypatch):
        """End to end through each trainer that calls
        ``partial_normal_eq``: the gather is exact, so the packed form
        trains the plain form's factor tables to the bit."""
        from pio_tpu.models import als

        s = synthetic
        r = np.abs(s["r"]) if implicit else s["r"]
        cfg = ALSConfig(rank=8, iterations=4, reg=0.05, implicit=implicit,
                        alpha=2.0, blocks_per_chunk=64)
        if path == "streamed":
            monkeypatch.setenv("PIO_TPU_ALS_STREAM_MB", "0.0005")
        ctx = (ComputeContext.create() if path == "mesh"
               else ComputeContext.local())

        def train(want):
            st = {}
            f = train_als(ctx, s["u"], s["i"], r, s["U"], s["I"], cfg,
                          stats=st)
            assert st["gather_impl"] == {"user": want, "item": want}
            assert (st["n_stream"] > 1) == (path == "streamed")
            return f

        plain = train("plain")  # the real rule: on CPU, the table as it is
        _steer_packed(monkeypatch, als)
        fresh_trainers()
        packed = train("packed")
        assert np.isfinite(plain.user_factors).all()
        assert np.array_equal(packed.user_factors, plain.user_factors)
        assert np.array_equal(packed.item_factors, plain.item_factors)


def _steer_packed(monkeypatch, als):
    """The rule as a TPU would read it of a table over the threshold
    (the caller empties the trainers' caches: the rule is read when a
    trainer is traced)."""
    rule = als._gather_impl
    monkeypatch.setattr(
        als, "_gather_impl",
        lambda platform, n_rows, rank, itemsize: rule(
            "tpu", 162_541, rank, 2))


def _steer_fused(monkeypatch, als):
    """The rule as a TPU would read it of bfloat16 operands (the caller
    empties the trainers' caches: the rule is read when a trainer is
    traced); off a TPU ``partial_normal_eq`` then interprets the kernel."""
    rule = als._accum_impl
    monkeypatch.setattr(
        als, "_accum_impl",
        lambda platform, rank, width, itemsize: rule("tpu", rank, width, 2))


def _block_lists(case):
    """``(block_ent, n_entities, chunk)`` at the kernel's width: ascending
    entity ids, in chunks of 128 blocks (two tiles of 64) but for one."""
    if case == "straddles":
        # entity 7 owns blocks 60..69 (across the tile boundary at 64),
        # entity 11 blocks 120..139 (across the chunk boundary at 128),
        # entity 30 the odd-length run 250..260 (pairs split in the middle)
        runs = ([9, 8, 9, 8, 9, 8, 9, 10, 20, 15, 15, 20, 7, 7] + [6] * 16
                + [11] + [1] * 123)
        ent = np.repeat(np.arange(len(runs)), runs)
        assert len(ent) == 384 and ent[60] == ent[69] == 7
        assert ent[120] == ent[139] == 11 and ent[250] == ent[260] == 30
        return ent, 210, 128
    if case == "whole_chunk":
        # entity 5 owns all of the second chunk and more (the cell's
        # ``max_degree`` 32,768 is 512 blocks of 64)
        ent = np.repeat(np.arange(90), [20] * 5 + [328] + [1] * 84)
        assert len(ent) == 512 and ent[100] == ent[427] == 5
        return ent, 90, 128
    if case == "padded":
        # ids with gaps (entities that no block names), padded entities
        # beyond the last id, padding blocks that alias the last entity
        ids = np.arange(0, 120, 3)
        ent = np.concatenate([np.repeat(ids, 5), np.full(56, ids[-1])])
        assert len(ent) == 256
        return ent, 160, 128
    if case == "ragged_chunk":
        # a chunk that no tile of 16 blocks divides: padded in the wrapper
        return np.repeat(np.arange(12), 6), 14, 24
    assert case == "one_chunk"
    return np.repeat(np.arange(16), 8), 16, 128


class TestFusedAccum:
    """The kernel that multiplies a chunk's blocks and sums them per
    entity in VMEM (``_accum_fused``, interpreted here) against the
    einsum and ``segment_sum`` it replaces on a TPU (the oracle), and the
    rule that chooses between them (``_accum_impl``)."""

    @pytest.mark.parametrize("case", [
        "straddles", "whole_chunk", "padded", "one_chunk", "ragged_chunk"])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("implicit", [False, True],
                             ids=["explicit", "implicit"])
    def test_kernel_matches_xla_path(self, implicit, dtype, case,
                                     monkeypatch):
        import jax

        from pio_tpu.models import als

        block_ent, n, chunk = _block_lists(case)
        S, W, K, n_other = len(block_ent), 64, 64, 300
        rng = np.random.default_rng(S)
        other = rng.integers(0, n_other, (S, W)).astype(np.int32)
        other[rng.random((S, W)) < 0.3] = -1
        if case == "padded":
            other[-56:] = -1  # the padding blocks carry no edge
        r = (rng.integers(1, 11, (S, W)) * 0.5).astype(np.float32)
        table = (np.abs(rng.standard_normal((n_other, K))) / 8
                 ).astype(np.float32)

        def normal_eq():
            math = als._make_math(0.1, implicit, 2.0, dtype, "cg")
            A, b = jax.jit(
                lambda e, o, r, t: math.partial_normal_eq(
                    e, o, r, t, n, chunk)
            )(block_ent.astype(np.int32), other, r, table)
            return np.asarray(A), np.asarray(b)

        want_A, want_b = normal_eq()  # the real rule: on CPU, XLA's path
        _steer_fused(monkeypatch, als)
        got_A, got_b = normal_eq()
        assert got_A.shape == (n, K, K) and got_b.shape == (n, K)
        # float32 sums in another order; on CPU XLA also keeps the
        # weighted bfloat16 rows unrounded (the kernel rounds them, as the
        # chip does), which shows where the weights are no 0/1 mask
        tol = 4e-3 if implicit and dtype == "bfloat16" else 2e-6
        for got, want in ((got_A, want_A), (got_b, want_b)):
            assert np.abs(got - want).max() <= tol * np.abs(want).max()
        named = np.zeros(n, bool)
        named[block_ent] = True
        assert not got_A[~named].any() and not got_b[~named].any()
        assert got_A[named].any(axis=(1, 2)).all()

    @pytest.mark.parametrize("platform,rank,width,itemsize,want", [
        ("tpu", 64, 64, 2, "fused"),      # the benchmark cell
        ("cpu", 64, 64, 2, "xla"),
        ("gpu", 64, 64, 2, "xla"),
        ("tpu", 64, 64, 4, "xla"),        # float32 operands
        ("tpu", 10, 64, 2, "xla"),        # the template's default
        ("tpu", 16, 64, 2, "xla"),        # not measured: PERF.md
        ("tpu", 128, 64, 2, "xla"),
        ("tpu", 64, 32, 2, "xla"),
        ("tpu", 64, 16, 2, "xla"),
    ])
    def test_selection_rule(self, platform, rank, width, itemsize, want):
        from pio_tpu.models.als import _accum_impl

        assert _accum_impl(platform, rank, width, itemsize) == want

    def test_stats_and_run_record_name_the_path(self, fresh_trainers,
                                                monkeypatch):
        """``xla`` on CPU by the rule, ``fused`` once steered; ``stats``
        and the run record carry it per half-step."""
        from pio_tpu.models import als
        from pio_tpu.obs import trainwatch

        u, i, r, U, I = _wide_ratings()

        def train():
            st = {}
            recorder = trainwatch.StepRecorder("accum-impl")
            with trainwatch.recording(recorder):
                train_als(ComputeContext.local(), u, i, r, U, I,
                          ALSConfig(rank=64, iterations=1, block_width=64),
                          stats=st)
            record = trainwatch.run_record(
                run_id="r", engine_id="e", status="COMPLETED",
                train_seconds=1.0, phases={}, params_hash="h",
                step_summary=recorder.summary())
            assert record["accum_impl"] == st["accum_impl"]
            return st["accum_impl"]

        assert train() == {"user": "xla", "item": "xla"}
        _steer_fused(monkeypatch, als)
        fresh_trainers()
        assert train() == {"user": "fused", "item": "fused"}

    @pytest.mark.parametrize("implicit", [False, True],
                             ids=["explicit", "implicit"])
    @pytest.mark.parametrize("path", ["monolithic", "streamed", "mesh"])
    def test_train_als_on_the_interpreted_kernel(self, path, implicit,
                                                 fresh_trainers,
                                                 monkeypatch):
        """End to end through each trainer that calls
        ``partial_normal_eq`` (the mesh route under ``shard_map``): the
        kernel (interpreted) trains the model XLA's path trains."""
        from pio_tpu.models import als

        u, i, r, U, I = _wide_ratings()
        if implicit:
            r = np.abs(r)
        cfg = ALSConfig(rank=64, iterations=3, reg=0.05, implicit=implicit,
                        alpha=2.0, block_width=64, blocks_per_chunk=16)
        if path == "streamed":
            monkeypatch.setenv("PIO_TPU_ALS_STREAM_MB", "0.005")
        ctx = (ComputeContext.create() if path == "mesh"
               else ComputeContext.local())

        def train(want):
            st = {}
            f = train_als(ctx, u, i, r, U, I, cfg, stats=st)
            assert st["accum_impl"] == {"user": want, "item": want}
            assert (st["n_stream"] > 1) == (path == "streamed")
            return f.user_factors @ f.item_factors.T

        want = train("xla")  # the real rule: on CPU, XLA's path
        _steer_fused(monkeypatch, als)
        fresh_trainers()
        got = train("fused")
        assert np.isfinite(got).all()
        assert np.abs(got - want).max() <= 1e-3 * np.abs(want).max()


def _wide_ratings():
    """24 users x 150 items, 80% observed: a user's adjacency is two
    blocks of 64, and the by-user layout several chunks of 16 blocks."""
    rng = np.random.default_rng(3)
    U, I, K = 24, 150, 4
    R = rng.normal(size=(U, K)) @ rng.normal(size=(I, K)).T
    u, i = np.nonzero(rng.random((U, I)) < 0.8)
    return u, i, R[u, i].astype(np.float32), U, I


class TestTopN:
    def test_basic(self):
        scores = np.array([0.1, 5.0, 3.0, 4.0])
        idx, vals = top_n(scores, 2)
        assert idx.tolist() == [1, 3]
        assert vals.tolist() == [5.0, 4.0]

    def test_exclude(self):
        scores = np.array([0.1, 5.0, 3.0, 4.0])
        idx, _ = top_n(scores, 2, exclude=np.array([1]))
        assert idx.tolist() == [3, 2]

    def test_n_larger_than_items(self):
        idx, _ = top_n(np.array([1.0, 2.0]), 10)
        assert idx.tolist() == [1, 0]
