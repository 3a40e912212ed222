"""Interpret-mode parity of the kernel building blocks that the TPU
compiler forced: the masked-reduction selection network shared by the
topk, verify and pair-join kernels, the matmul rank that replaced a
cumsum in the radius select, and the select's tiled output."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.kernels import ops, ref
from repro.kernels.select import _concat_tiles, pack_front, tile_ranks
from repro.kernels.topk import smallest_k


def _in_kernel(fn, args, out_shapes):
    """Run ``fn`` on whole-array blocks inside an interpret-mode kernel."""
    def kern(*refs):
        outs = fn(*[r[...] for r in refs[:len(args)]])
        for r, o in zip(refs[len(args):], outs):
            r[...] = o

    return pl.pallas_call(kern, out_shape=out_shapes, interpret=True)(*args)


def _shape(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


class TestSmallestK:
    @pytest.mark.parametrize("rows,k,C", [(8, 10, 128), (8, 1, 256),
                                          (16, 33, 128)])
    def test_rowwise_matches_top_k_of_concatenation(self, rows, k, C):
        rng = np.random.default_rng(rows * k + C)
        # small integers: many ties, which must go to the earlier element
        acc = np.sort(rng.integers(0, 20, (rows, k)), axis=1).astype(np.float32)
        tile = rng.integers(0, 20, (rows, C)).astype(np.float32)
        acc_id = rng.integers(0, 10**6, (rows, k)).astype(np.int32)
        tile_id = (300 + np.arange(C, dtype=np.int32))[None].repeat(rows, 0)

        def fn(a, ai, t, ti):
            v, (i,) = smallest_k([(a, (ai,), (1,)), (t, (ti,), (1,))], k,
                                 rows)
            return v, i

        gv, gi = _in_kernel(fn, (acc, acc_id, tile, tile_id),
                            [_shape((rows, k), jnp.float32),
                             _shape((rows, k), jnp.int32)])
        pool = np.concatenate([acc, tile], axis=1)
        ids = np.concatenate([acc_id, tile_id], axis=1)
        neg, pos = jax.lax.top_k(-jnp.asarray(pool), k)
        np.testing.assert_array_equal(gv, -neg)
        np.testing.assert_array_equal(gi, np.take_along_axis(ids, pos, 1))

    def test_two_dim_piece_is_row_major(self):
        """The pair-join pool: a (1, k) heap then a 2-D tile read
        row-major, one global answer."""
        rng = np.random.default_rng(4)
        k, S, C = 12, 16, 128
        acc = np.sort(rng.integers(0, 9, (1, k)), axis=1).astype(np.float32)
        tile = rng.integers(0, 9, (S, C)).astype(np.float32)
        ai = rng.integers(0, 1000, (1, k)).astype(np.int32)
        aj = ai + 1
        gi = np.broadcast_to(np.arange(S, dtype=np.int32)[:, None] + 5000,
                             (S, C)).copy()
        gj = np.broadcast_to(np.arange(C, dtype=np.int32)[None] + 9000,
                             (S, C)).copy()

        def fn(a, ai_, aj_, t, gi_, gj_):
            v, (i, j) = smallest_k(
                [(a, (ai_, aj_), (1,)), (t, (gi_, gj_), (0, 1))], k, 1)
            return v, i, j

        gv, pi, pj = _in_kernel(fn, (acc, ai, aj, tile, gi, gj),
                                [_shape((1, k), jnp.float32),
                                 _shape((1, k), jnp.int32),
                                 _shape((1, k), jnp.int32)])
        pool = np.concatenate([acc[0], tile.ravel()])
        order = np.argsort(pool, kind="stable")[:k]
        np.testing.assert_array_equal(np.asarray(gv)[0], pool[order])
        np.testing.assert_array_equal(
            np.asarray(pi)[0], np.concatenate([ai[0], gi.ravel()])[order])
        np.testing.assert_array_equal(
            np.asarray(pj)[0], np.concatenate([aj[0], gj.ravel()])[order])

    def test_each_element_taken_once(self):
        """Fewer finite values than k: the +inf tail is filled by
        distinct elements in pool order, never a repeat."""
        k = 6
        acc = np.full((8, k), np.inf, np.float32)
        acc_id = np.full((8, k), -1, np.int32)
        tile = np.full((8, 128), np.inf, np.float32)
        tile[:, 7] = 1.0
        tile[:, 3] = 2.0
        tile_id = np.arange(128, dtype=np.int32)[None].repeat(8, 0)

        def fn(a, ai, t, ti):
            v, (i,) = smallest_k([(a, (ai,), (1,)), (t, (ti,), (1,))], k, 8)
            return v, i

        gv, gi = _in_kernel(fn, (acc, acc_id, tile, tile_id),
                            [_shape((8, k), jnp.float32),
                             _shape((8, k), jnp.int32)])
        np.testing.assert_array_equal(np.asarray(gv)[:, :2], [[1.0, 2.0]] * 8)
        assert np.isinf(np.asarray(gv)[:, 2:]).all()
        # the tail is the heap's own (inf, -1) slots, in order
        np.testing.assert_array_equal(np.asarray(gi)[:, :2], [[7, 3]] * 8)
        assert (np.asarray(gi)[:, 2:] == -1).all()


class TestSelectParts:
    @pytest.mark.parametrize("density", [0.0, 0.1, 0.5, 1.0])
    def test_tile_ranks_equal_exclusive_cumsum(self, density):
        rng = np.random.default_rng(int(density * 10))
        mask = rng.random((16, 128)) < density
        (got,) = _in_kernel(lambda m: (tile_ranks(m),), (mask,),
                            [_shape((16, 128), jnp.int32)])
        want = np.cumsum(mask, axis=1) - mask
        np.testing.assert_array_equal(got, want)

    def test_pack_front_keeps_lane_order(self):
        rng = np.random.default_rng(11)
        vals = rng.random((8, 128)).astype(np.float32) * 1e4
        mask = rng.random((8, 128)) < 0.3

        def fn(v, m):
            packed, lanes, keep = pack_front(v, m)
            return packed, lanes, keep.astype(jnp.int32)

        packed, lanes, keep = _in_kernel(
            fn, (vals, mask), [_shape((8, 128), jnp.float32),
                               _shape((8, 128), jnp.int32),
                               _shape((8, 128), jnp.int32)])
        for r in range(8):
            src = np.flatnonzero(mask[r])
            c = src.size
            np.testing.assert_array_equal(np.asarray(lanes)[r, :c], src)
            np.testing.assert_array_equal(np.asarray(packed)[r, :c],
                                          vals[r, src])  # bit-exact
            assert np.asarray(keep)[r, :c].all()
            assert not np.asarray(keep)[r, c:].any()

    def test_concat_tiles_matches_compaction(self):
        """Per-tile front-packed runs joined in index order, truncated
        at T_pad, with the exact survivor count."""
        rng = np.random.default_rng(5)
        B, bN, n_tiles, T_pad = 3, 128, 6, 200
        mask = rng.random((B, n_tiles * bN)) < 0.3
        mask[2] = False  # a row with no survivors at all
        d = rng.random((B, n_tiles * bN)).astype(np.float32)
        vals_t = np.full_like(d, np.inf)
        idx_t = np.full(d.shape, -1, np.int32)
        for b in range(B):
            for t in range(n_tiles):
                cols = t * bN + np.flatnonzero(mask[b, t * bN:(t + 1) * bN])
                vals_t[b, t * bN:t * bN + cols.size] = d[b, cols]
                idx_t[b, t * bN:t * bN + cols.size] = cols
        vals, idx, count = _concat_tiles(jnp.asarray(vals_t),
                                         jnp.asarray(idx_t), bN, T_pad)
        for b in range(B):
            cols = np.flatnonzero(mask[b])
            assert int(count[b]) == cols.size
            c = min(cols.size, T_pad)
            np.testing.assert_array_equal(np.asarray(idx)[b, :c], cols[:c])
            np.testing.assert_array_equal(np.asarray(vals)[b, :c],
                                          d[b, cols[:c]])
            assert (np.asarray(idx)[b, c:] == -1).all()
            assert np.isinf(np.asarray(vals)[b, c:]).all()

    @pytest.mark.parametrize("force", ["interpret", "ref"])
    def test_select_ids_past_bf16_range(self, force):
        """Every selected id is > 256 and comes through the select
        exact.  On the CPU a float32 matmul never takes a bf16 pass, so
        this does not see the chip's precision: that the select's
        matmul asks for float32 is checked on the lowered kernel in
        ``test_tpu_compile.py``."""
        rng = np.random.default_rng(21)
        n, T = 5000, 60
        d = rng.uniform(10.0, 20.0, (3, n)).astype(np.float32)
        hot = np.stack([rng.choice(np.arange(4000, n), T, replace=False)
                        for _ in range(3)])
        for b in range(3):
            d[b, hot[b]] = rng.uniform(0.0, 1.0, T).astype(np.float32)
        got_v, got_i = ops.radius_select(jnp.asarray(d), T, T_pad=T + 64,
                                         force=force)
        want_v, want_i = ref.topk_smallest(jnp.asarray(d), T)
        np.testing.assert_array_equal(got_i, want_i)
        np.testing.assert_array_equal(got_v, want_v)
        assert (np.asarray(got_i) > 256).all()
