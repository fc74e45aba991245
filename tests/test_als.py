"""ALS model tests: reconstruction quality, mesh-vs-local parity, implicit
mode, edge cases. Runs on the simulated 8-device CPU mesh (conftest)."""

import numpy as np
import pytest

from pio_tpu.models.als import ALSConfig, top_n, train_als
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

    def test_mesh_compact_wire_matches_blocked(self, synthetic,
                                               monkeypatch):
        """The compact mesh wire (sharded h2d → ICI all-gather → device
        dual-layout construction) must train BYTE-IDENTICAL factors to
        the host-packed blocked-f32 shipment — the two paths feed the
        same shard_map trainer and device_pack is bit-identical to the
        host packers. Grid ratings make the u4 rating decode exact."""
        s = synthetic
        rng = np.random.default_rng(5)
        r_grid = (rng.integers(1, 11, len(s["u"])) * 0.5).astype(np.float32)

        monkeypatch.setenv("PIO_TPU_ALS_MESH_WIRE", "blocked")
        st_b = {}
        f_blocked = train_als(
            ComputeContext.create(), s["u"], s["i"], r_grid,
            s["U"], s["I"], CFG, stats=st_b,
        )
        assert st_b["encoding"] == "blocked-f32"

        monkeypatch.setenv("PIO_TPU_ALS_MESH_WIRE", "compact")
        st_c = {}
        f_compact = train_als(
            ComputeContext.create(), s["u"], s["i"], r_grid,
            s["U"], s["I"], CFG, stats=st_c,
        )
        assert st_c["encoding"].startswith("u4"), st_c
        assert np.array_equal(
            f_blocked.user_factors, f_compact.user_factors
        )
        assert np.array_equal(
            f_blocked.item_factors, f_compact.item_factors
        )
        # the whole point: the compact wire crosses the host link with a
        # small fraction of the blocked-f32 bytes
        assert st_c["wire_bytes"] < st_b["wire_bytes"] / 3, (st_c, st_b)

    def test_mesh_compact_wire_chunked_stream(self, synthetic,
                                              monkeypatch):
        """PIO_TPU_ALS_STREAM_MB applies to the mesh path too: the
        encoded wire ships as multiple sharded spans (pipelined puts)
        and the trainer splices them back — factors stay byte-identical
        to blocked-f32 and the stats record the per-chunk timings."""
        s = synthetic
        rng = np.random.default_rng(7)
        r_grid = (rng.integers(1, 11, len(s["u"])) * 0.5).astype(np.float32)

        monkeypatch.setenv("PIO_TPU_ALS_MESH_WIRE", "blocked")
        f_blocked = train_als(
            ComputeContext.create(), s["u"], s["i"], r_grid,
            s["U"], s["I"], CFG,
        )
        monkeypatch.setenv("PIO_TPU_ALS_MESH_WIRE", "compact")
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

    def test_mesh_compact_planes_wire_with_high_plane(self, monkeypatch):
        """Items ≥ 2^16 force the planes wire with a NON-EMPTY high
        plane — that array rides the sharded put + slice path too and
        must stay byte-identical to blocked."""
        rng = np.random.default_rng(11)
        n = 3000
        u = rng.integers(0, 40, n).astype(np.int32)
        i = rng.integers(0, 70_000, n).astype(np.int32)
        r = (rng.integers(1, 11, n) * 0.5).astype(np.float32)
        cfg = ALSConfig(rank=4, iterations=4, reg=0.05,
                        blocks_per_chunk=16)
        monkeypatch.setenv("PIO_TPU_ALS_MESH_WIRE", "blocked")
        f_b = train_als(ComputeContext.create(), u, i, r, 40, 70_000, cfg)
        monkeypatch.setenv("PIO_TPU_ALS_MESH_WIRE", "compact")
        st = {}
        f_c = train_als(ComputeContext.create(), u, i, r, 40, 70_000,
                        cfg, stats=st)
        assert st["encoding"].endswith("planes"), st
        assert np.array_equal(f_b.user_factors, f_c.user_factors)
        assert np.array_equal(f_b.item_factors, f_c.item_factors)

    def test_mesh_compact_delta_overflow(self, monkeypatch):
        """Within-user item gaps > 4095 exercise the sparse overflow
        list on the mesh wire; factors must match blocked exactly."""
        rng = np.random.default_rng(12)
        n_users, n_items = 24, 60_000
        us, its = [], []
        for uu in range(n_users):
            # a handful of items spread across the full range → most
            # consecutive gaps exceed 4095
            for ii in range(0, n_items, 7013):
                us.append(uu)
                its.append((ii + uu * 311) % n_items)
        u = np.array(us, np.int32)
        i = np.array(its, np.int32)
        r = (rng.integers(1, 11, len(u)) * 0.5).astype(np.float32)
        cfg = ALSConfig(rank=4, iterations=3, reg=0.05,
                        blocks_per_chunk=16)
        monkeypatch.setenv("PIO_TPU_ALS_ITEM_WIRE", "delta12")
        monkeypatch.setenv("PIO_TPU_ALS_MESH_WIRE", "blocked")
        f_b = train_als(ComputeContext.create(), u, i, r,
                        n_users, n_items, cfg)
        monkeypatch.setenv("PIO_TPU_ALS_MESH_WIRE", "compact")
        st = {}
        f_c = train_als(ComputeContext.create(), u, i, r,
                        n_users, n_items, cfg, stats=st)
        assert st["encoding"].endswith("delta12"), st
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

    def test_native_packer_matches_numpy(self):
        """C++ packer (pio_tpu/native/als_pack.cpp) must be bit-identical
        to the numpy reference layout."""
        from pio_tpu.models.als import (
            _f32p, _i32p, _i64p, _native_packer, _pack_blocks, _round_up,
        )

        native = _native_packer()
        if native is None:
            pytest.skip("no native toolchain")
        rng = np.random.default_rng(11)
        E, N, W = 50_000, 700, 16
        ent = rng.integers(0, N, E).astype(np.int32)
        other = rng.integers(0, 9999, E).astype(np.int32)
        rat = rng.random(E).astype(np.float32)
        ref = _pack_blocks(ent, other, rat, N, W, 64)
        S = ref[0].shape[0]
        counts = np.zeros(N, np.int64)
        nb = int(native.als_pack_count(_i32p(ent), E, N, W, _i64p(counts)))
        assert S == max(64, _round_up(nb, 64))
        be = np.empty(S, np.int32)
        bo = np.empty(S * W, np.int32)
        br = np.empty(S * W, np.float32)
        native.als_pack_fill(
            _i32p(ent), _i32p(other), _f32p(rat), E, N, W,
            _i64p(counts), S, _i32p(be), _i32p(bo), _f32p(br),
        )
        assert (be == ref[0]).all()
        assert (bo.reshape(S, W) == ref[1]).all()
        assert (br.reshape(S, W) == ref[2]).all()

    def test_native_sort_by_entity_matches_numpy(self):
        """C++ counting sort (the counts wire-format producer) must match
        numpy's stable argsort exactly."""
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
        """Single-device training must not depend on which host packer
        produced the wire format (same stable edge order → same floats)."""
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
        """Ratings off the uint8/fp16 grids ride the f32 wire fallback."""
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

    def test_device_pack_matches_host_packers(self):
        """The on-device packer must be bit-identical to the host layout
        (the trainer's correctness rides on ascending block_ent for
        indices_are_sorted segment sums and -1 padding sentinels)."""
        import jax
        import jax.numpy as jnp

        from pio_tpu.models.als import (
            _pack_blocks, _round_up, device_pack,
        )

        rng = np.random.default_rng(21)
        for E, N, W in [(5000, 80, 16), (1, 4, 8), (64, 4, 8), (97, 200, 8)]:
            ent = rng.integers(0, N, E).astype(np.int32)
            oth = rng.integers(0, 999, E).astype(np.int32)
            rat = rng.random(E).astype(np.float32)
            ref = _pack_blocks(ent, oth, rat, N, W, 8)
            S = ref[0].shape[0]
            got = jax.jit(
                device_pack, static_argnums=(3, 4, 5)
            )(jnp.asarray(ent), jnp.asarray(oth), jnp.asarray(rat), N, W, S)
            assert (np.asarray(got[0]) == ref[0]).all(), (E, N, W)
            assert (np.asarray(got[1]) == ref[1]).all(), (E, N, W)
            assert (np.asarray(got[2]) == ref[2]).all(), (E, N, W)

    def test_wide_id_space_plane_encoding(self):
        """Entity ids in [2^16, 2^24) ship as uint16+uint8 planes; a
        mis-widened id would train the wrong rows."""
        rng = np.random.default_rng(5)
        hi_users = [65_536, 70_000, 99_999]  # beyond the uint16 range
        u = np.array(hi_users * 40, np.int32)
        i = rng.integers(0, 8, len(u)).astype(np.int32)
        R = rng.normal(size=(3, 8)).astype(np.float32)
        r = np.array(
            [R[hi_users.index(uu), ii] for uu, ii in zip(u, i)], np.float32
        )
        f = train_als(
            ComputeContext.local(), u, i, r, 100_000, 8,
            ALSConfig(rank=4, iterations=10, reg=0.05),
        )
        pred = (f.user_factors[u] * f.item_factors[i]).sum(1)
        rmse = float(np.sqrt(np.mean((pred - r) ** 2)))
        assert rmse < 0.1, rmse
        # untouched rows stay at their tiny init scale
        assert np.abs(f.user_factors[500]).max() < 0.05

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

    def test_streamed_matches_monolithic(self, synthetic, monkeypatch):
        """The double-buffered chunked shipment must train the same model
        as the single-dispatch path (it differs only in iteration-1
        accumulation grouping — float reduction order)."""
        s = synthetic
        f_mono = train_als(
            ComputeContext.local(), s["u"], s["i"], s["r"], s["U"], s["I"],
            CFG,
        )
        # ~KB-scale threshold forces the max 8 stream chunks on this data
        monkeypatch.setenv("PIO_TPU_ALS_STREAM_MB", "0.0005")
        stats = {}
        f_str = train_als(
            ComputeContext.local(), s["u"], s["i"], s["r"], s["U"], s["I"],
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

    def test_streamed_u4_ratings(self, synthetic, monkeypatch):
        """Half-star-grid ratings ride the nibble-packed u4 wire; the
        decode is exact, so streamed-vs-monolithic differences reduce to
        reduction-order float noise."""
        s = synthetic
        rng = np.random.default_rng(9)
        r_grid = (rng.integers(1, 11, len(s["u"])) * 0.5).astype(np.float32)
        stats = {}
        f_mono = train_als(
            ComputeContext.local(), s["u"], s["i"], r_grid, s["U"], s["I"],
            CFG, stats=stats,
        )
        assert stats["encoding"].startswith("u4"), stats
        monkeypatch.setenv("PIO_TPU_ALS_STREAM_MB", "0.0005")
        stats2 = {}
        f_str = train_als(
            ComputeContext.local(), s["u"], s["i"], r_grid, s["U"], s["I"],
            CFG, stats=stats2,
        )
        assert stats2["n_stream"] > 1
        assert stats2["encoding"].startswith("u4")
        # the two paths saw identical decoded floats (u4 is exact), so
        # they may differ only by reduction-order noise
        pm = f_mono.user_factors @ f_mono.item_factors.T
        ps = f_str.user_factors @ f_str.item_factors.T
        assert np.abs(pm - ps).max() < 0.05

    def test_delta_item_wire_roundtrip(self):
        """The 12-bit delta item wire must reproduce ids EXACTLY (numpy
        reference of the device decode, overflow gaps included)."""
        from pio_tpu.models.als import _encode_items_delta

        rng = np.random.default_rng(3)
        # segmented ids with deliberate >4095 gaps and duplicate items
        counts = np.array([0, 5, 0, 3, 1, 7, 0], np.int64)
        ids = []
        for c in counts:
            row = np.sort(rng.integers(0, 60000, c))
            ids.extend(row.tolist())
        ids = np.array(ids, np.int32)
        d_lo, d_hi, ovf_idx, ovf_val, nbytes = _encode_items_delta(
            ids, counts
        )
        assert nbytes == d_lo.nbytes + d_hi.nbytes + ovf_idx.nbytes \
            + ovf_val.nbytes
        # numpy mirror of _make_math.decode_items("delta12")
        E = len(ids)
        hi = np.stack([d_hi & 0xF, d_hi >> 4], 1).reshape(-1)[:E]
        delta = d_lo.astype(np.uint32) | (hi.astype(np.uint32) << 8)
        delta[ovf_idx] += ovf_val.astype(np.uint32) << 12
        G = np.cumsum(delta, dtype=np.uint32)
        cnt = counts[counts > 0]
        starts = np.zeros(len(cnt), np.int64)
        np.cumsum(cnt[:-1], out=starts[1:])
        prev = np.zeros(E, np.uint32)
        es = np.repeat(np.where(starts > 0, G[starts - 1], 0), cnt)
        got = (G - es).astype(np.int32)
        assert (got == ids).all()

    def test_item_wire_formats_agree_bitwise(self, synthetic, monkeypatch):
        """delta12 decode is integer-exact, so forcing planes vs delta12
        must give BITWISE identical factors (same sorted edge order →
        same floats through the same math)."""
        s = synthetic
        outs = {}
        for wire in ("planes", "delta12"):
            monkeypatch.setenv("PIO_TPU_ALS_ITEM_WIRE", wire)
            outs[wire] = train_als(
                ComputeContext.local(), s["u"], s["i"], s["r"],
                s["U"], s["I"], CFG,
            )
        assert (outs["planes"].user_factors
                == outs["delta12"].user_factors).all()
        assert (outs["planes"].item_factors
                == outs["delta12"].item_factors).all()

    def test_item_wire_formats_agree_streamed(self, synthetic,
                                              monkeypatch):
        """Same bitwise equality through the chunked stream path (the
        delta wire restarts gap chains at chunk boundaries)."""
        s = synthetic
        monkeypatch.setenv("PIO_TPU_ALS_STREAM_MB", "0.0005")
        outs = {}
        for wire in ("planes", "delta12"):
            monkeypatch.setenv("PIO_TPU_ALS_ITEM_WIRE", wire)
            st = {}
            outs[wire] = train_als(
                ComputeContext.local(), s["u"], s["i"], s["r"],
                s["U"], s["I"], CFG, stats=st,
            )
            assert st["n_stream"] > 1
        assert (outs["planes"].user_factors
                == outs["delta12"].user_factors).all()
        assert (outs["planes"].item_factors
                == outs["delta12"].item_factors).all()

    def test_streamed_delta_overflow_and_chunk_carry(self, monkeypatch):
        """Sparse adjacencies over a wide item space: deltas overflow the
        12-bit field (sparse overflow list) AND chunk boundaries split
        users mid-adjacency (the first in-chunk edge ships its ABSOLUTE
        id, itself often an overflow). Streamed delta12 must still match
        planes bitwise."""
        from pio_tpu.models.als import _delta_wire_size

        rng = np.random.default_rng(17)
        U, I, E = 25, 50_000, 1_200
        u = np.sort(rng.integers(0, U, E)).astype(np.int32)
        i = rng.integers(0, I, E).astype(np.int32)  # mean gap ~2k, tail >4095
        r = (rng.integers(1, 11, E) * 0.5).astype(np.float32)
        # sanity: this workload really produces overflow entries
        order = np.lexsort((i, u))
        counts = np.bincount(u, minlength=U).astype(np.int64)
        _, n_ovf = _delta_wire_size(
            np.ascontiguousarray(i[order]), counts
        )
        assert n_ovf > 0, "fixture must exercise the overflow list"

        cfg = ALSConfig(rank=4, iterations=5, reg=0.1, blocks_per_chunk=16)
        monkeypatch.setenv("PIO_TPU_ALS_STREAM_MB", "0.0002")  # many chunks
        outs = {}
        for wire in ("planes", "delta12"):
            monkeypatch.setenv("PIO_TPU_ALS_ITEM_WIRE", wire)
            st = {}
            outs[wire] = train_als(
                ComputeContext.local(), u, i, r, U, I, cfg, stats=st
            )
            assert st["n_stream"] > 1, st
        assert (outs["planes"].user_factors
                == outs["delta12"].user_factors).all()
        assert (outs["planes"].item_factors
                == outs["delta12"].item_factors).all()

    def test_native_delta_encoder_matches_numpy(self, monkeypatch):
        """The C++ delta encoder must be bit-identical to the numpy
        reference (wire format parity, overflow entries included)."""
        from pio_tpu.models.als import (
            _delta_wire_size, _encode_items_delta, _native_packer,
        )

        if _native_packer() is None:
            pytest.skip("no native toolchain")
        rng = np.random.default_rng(12)
        counts = rng.integers(0, 40, 300).astype(np.int64)
        ids = np.concatenate([
            np.sort(rng.integers(0, 60000, c)) for c in counts
        ]).astype(np.int32)
        got_native = _encode_items_delta(ids, counts)
        nb_native, novf_native = _delta_wire_size(ids, counts)
        monkeypatch.setenv("PIO_TPU_NO_NATIVE", "1")
        got_numpy = _encode_items_delta(ids, counts)
        nb_numpy, novf_numpy = _delta_wire_size(ids, counts)
        assert nb_native == nb_numpy == got_native[4]
        assert novf_native == novf_numpy == len(got_native[2])
        for a, b in zip(got_native[:4], got_numpy[:4]):
            assert a.dtype == b.dtype and (a == b).all()

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

    def test_nibble_roundtrip(self):
        from pio_tpu.models.als import _encode_ratings, _nibble_pack

        codes = np.array([1, 10, 7, 15, 0, 3, 9], np.uint8)  # odd length
        packed = _nibble_pack(codes)
        assert packed.shape == (4,)
        lo, hi = packed & 0xF, packed >> 4
        inter = np.stack([lo, hi], 1).reshape(-1)[: len(codes)]
        assert (inter == codes).all()
        wire, kind = _encode_ratings(codes.astype(np.float32) * 0.5)
        assert kind == "u4" and (wire == packed).all()
        # beyond the nibble range → u8; off-grid → f16/f32
        assert _encode_ratings(np.array([8.5], np.float32))[1] == "u8"
        assert _encode_ratings(np.array([0.123], np.float32))[1] in (
            "f16", "f32"
        )

    def test_stats_phases(self, synthetic):
        """Profiling mode fills the per-phase breakdown on every path."""
        s = synthetic
        for ctx in (ComputeContext.local(), ComputeContext.create()):
            st = {}
            train_als(ctx, s["u"], s["i"], s["r"], s["U"], s["I"], CFG,
                      stats=st)
            for k in ("pack_s", "wire_bytes", "h2d_s", "device_s",
                      "n_stream", "encoding"):
                assert k in st, (k, st)
            assert st["wire_bytes"] > 0 and st["device_s"] > 0

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
