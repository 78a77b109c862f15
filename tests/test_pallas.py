"""Pallas embedding-bag kernel tests (interpret mode on the CPU mesh).

Oracle is the plain-XLA gather (`embedding_bag_reference`), itself golden-
tested against torch in test_ops_golden.py — the same two-level scheme as
the reference's CUDA-kernel-vs-PyTorch harness (src/ops/tests/).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dlrm_flexflow_tpu.ops.pallas import embedding_kernel
from dlrm_flexflow_tpu.ops.pallas.embedding_kernel import (
    embedding_bag, embedding_bag_reference, stacked_embedding_bag, supports)


def _mk(rows, dim, batch, bag, seed=0):
    rng = np.random.RandomState(seed)
    table = rng.randn(rows, dim).astype(np.float32)
    idx = rng.randint(0, rows, size=(batch, bag)).astype(np.int32)
    return jnp.asarray(table), jnp.asarray(idx)


class TestEmbeddingBagKernel:
    @pytest.mark.parametrize("dim,bag,batch", [
        (128, 1, 16), (128, 3, 17), (256, 2, 8), (384, 1, 5)])
    def test_forward_matches_oracle(self, dim, bag, batch):
        table, idx = _mk(200, dim, batch, bag)
        out = embedding_bag(table, idx, "sum", True)
        ref = embedding_bag_reference(table, idx, "sum")
        np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("dim,batch", [
        (128, 3), (128, 257), (128, 300), (128, 1000), (256, 300)])
    def test_bag_of_one_is_a_bit_identical_copy(self, dim, batch):
        """bag 1 is a copy through the landing buffers: below one sublane
        tile, one past a block, a partly padded last block, several
        blocks (so both buffers, and the block started a step ahead), and
        two chunks a row (two planes)."""
        table, idx = _mk(500, dim, batch, 1, seed=batch)
        out = embedding_bag(table, idx, "sum", True)
        np.testing.assert_array_equal(out, jnp.take(table, idx[:, 0], axis=0))

    def test_bag_spanning_two_blocks(self):
        batch, bag, dim = 50, 3, 256
        block = embedding_kernel._gather_block(batch, bag, dim // 128)
        assert block < batch <= 2 * block
        table, idx = _mk(200, dim, batch, bag, seed=3)
        out = embedding_bag(table, idx, "sum", True)
        ref = embedding_bag_reference(table, idx, "sum")
        np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-6)

    def test_hot_row_fetched_a_block_over(self):
        """Every id equal: one row is in flight as many times as the block
        has slots."""
        table, _ = _mk(64, 128, 1, 1)
        idx = jnp.full((600, 1), 37, jnp.int32)
        out = embedding_bag(table, idx, "sum", True)
        np.testing.assert_array_equal(
            out, jnp.broadcast_to(table[37], (600, 128)))

    def test_first_and_last_row_in_the_padded_block(self):
        rows, batch = 400, 300
        block = embedding_kernel._gather_block(batch, 1, 1)
        assert batch % block, "the last block must be partly padding"
        table, idx = _mk(rows, 128, batch, 1, seed=5)
        idx = idx.at[-2:, 0].set(jnp.asarray([0, rows - 1], jnp.int32))
        out = embedding_bag(table, idx, "sum", True)
        np.testing.assert_array_equal(out, jnp.take(table, idx[:, 0], axis=0))

    def test_ids_outside_the_table_are_clamped(self):
        """The kernel runs without Mosaic's DMA bounds checks, so no id may
        reach it unclamped: below 0 reads row 0, past the end the last."""
        table, _ = _mk(40, 128, 1, 1)
        idx = jnp.asarray([[-5, 3], [40, 3], [2 ** 31 - 1, 3]], jnp.int32)
        out = embedding_bag(table, idx, "sum", True)
        ref = jnp.stack([table[0], table[39], table[39]]) + table[3]
        np.testing.assert_array_equal(out, ref)

    @pytest.mark.parametrize("batch,bag,k", [
        (89856, 1, 1), (3328, 1, 1), (5, 1, 3), (17, 3, 1), (50, 3, 2),
        (1000, 4, 1), (64, 40, 2)])
    def test_gather_block_bound(self, batch, bag, k):
        """`_gather_block`'s stated rule: a whole number of sublane tiles,
        no more than the batch rounded up to one, and at most
        _GATHER_FETCHES chunk fetches a block unless one sublane tile of
        samples already needs more."""
        g = embedding_kernel._gather_block(batch, bag, k)
        whole_batch = -(-batch // 8) * 8
        assert g >= 8 and g % 8 == 0
        assert g <= whole_batch
        assert g * bag * k <= max(embedding_kernel._GATHER_FETCHES,
                                  8 * bag * k)
        # the largest such block: one more sublane tile breaks the rule
        if g < whole_batch:
            assert (g + 8) * bag * k > embedding_kernel._GATHER_FETCHES

    def test_avg_mode(self):
        table, idx = _mk(100, 128, 9, 4)
        out = embedding_bag(table, idx, "avg", True)
        ref = embedding_bag_reference(table, idx, "avg")
        np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-6)

    def test_unsupported_dim_raises(self):
        table, idx = _mk(50, 64, 4, 1)
        assert not supports(64)
        with pytest.raises(ValueError, match="128"):
            embedding_bag(table, idx, "sum", True)

    @pytest.mark.parametrize("aggr", ["sum", "avg"])
    def test_gradient_matches_oracle(self, aggr):
        table, idx = _mk(80, 128, 11, 3)

        def f(t):
            return jnp.sum(embedding_bag(t, idx, aggr, True) ** 2)

        def fr(t):
            return jnp.sum(embedding_bag_reference(t, idx, aggr) ** 2)

        np.testing.assert_allclose(jax.grad(f)(table), jax.grad(fr)(table),
                                   rtol=1e-5, atol=1e-5)

    def test_duplicate_indices_grad(self):
        """scatter-add correctness: repeated rows accumulate (the case the
        reference needed atomicAdd for, embedding.cu backward)."""
        table = jnp.asarray(np.ones((10, 128), np.float32))
        idx = jnp.asarray(np.array([[3, 3], [3, 7]], np.int32))

        def f(t):
            return jnp.sum(embedding_bag(t, idx, "sum", True))

        g = jax.grad(f)(table)
        assert float(g[3, 0]) == pytest.approx(3.0)
        assert float(g[7, 0]) == pytest.approx(1.0)
        assert float(g[0, 0]) == 0.0

    def test_stacked_tables(self):
        rng = np.random.RandomState(1)
        tabs = jnp.asarray(rng.randn(4, 50, 128).astype(np.float32))
        idx = jnp.asarray(rng.randint(0, 50, size=(9, 4, 2)).astype(np.int32))
        out = stacked_embedding_bag(tabs, idx, "sum", True)
        ref = jnp.stack(
            [embedding_bag_reference(tabs[t], idx[:, t], "sum")
             for t in range(4)], axis=1)
        np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-6)

        def f(T):
            return jnp.sum(stacked_embedding_bag(T, idx, "sum", True) ** 2)

        def fr(T):
            return jnp.sum(jnp.stack(
                [embedding_bag_reference(T[t], idx[:, t], "sum")
                 for t in range(4)], axis=1) ** 2)

        np.testing.assert_allclose(jax.grad(f)(tabs), jax.grad(fr)(tabs),
                                   rtol=1e-5, atol=1e-5)


# every form of the two scatter kernels: (entry point, row width). "add" is
# scatter_add_rows (d=256: k = 2 chunks a row), "add_packed" and
# "write_packed" take the lane-packed (rows * d / 128, 128) view
SCATTER_FORMS = [("add", 128), ("add", 256), ("add_packed", 64),
                 ("add_packed", 16), ("write_packed", 64),
                 ("write_packed", 16)]


def _scatter_form(form, logical, idx, upd):
    """One scatter through the kernel (interpret mode) -> (rows, d)."""
    kind, d = form
    rows = logical.shape[0]
    if kind == "add":
        return np.asarray(embedding_kernel.scatter_add_rows(
            jnp.asarray(logical), jnp.asarray(idx), jnp.asarray(upd),
            interpret=True))
    r = 128 // d
    view = logical.reshape(rows // r, 128)
    if kind == "add_packed":
        got = embedding_kernel.scatter_add_rows_packed(
            jnp.asarray(view), jnp.asarray(idx), jnp.asarray(upd), d,
            interpret=True)
    else:
        # the forward's tiles; a lookup outside the table read none
        fwd_tiles = view[np.clip(idx // r, 0, rows // r - 1)]
        got = jax.jit(
            lambda v, i, u, t: embedding_kernel.scatter_write_rows_packed(
                v, i, u, t, d, interpret=True))(
                    jnp.asarray(view), jnp.asarray(idx), jnp.asarray(upd),
                    jnp.asarray(fwd_tiles))
    return np.asarray(got).reshape(rows, d)


def _scatter_tiles(rmw):
    """The kernel alone on deduped tiles: read-modify-write or write-only."""
    return (embedding_kernel._scatter_add_tiles if rmw
            else embedding_kernel.scatter_write_tiles)


def _check_scatter_form(form, rows, idx, seed=0):
    """Against numpy's add.at over the ids inside the table: an id below 0
    or at or past `rows` is dropped, as `.at[idx].add(mode="drop")` drops
    one past the end."""
    rng = np.random.RandomState(seed)
    logical = rng.rand(rows, form[1]).astype(np.float32)
    idx = np.asarray(idx, np.int32)
    upd = rng.rand(len(idx), form[1]).astype(np.float32)
    inside = (idx >= 0) & (idx < rows)
    want = logical.copy()
    np.add.at(want, idx[inside], upd[inside])
    got = _scatter_form(form, logical, idx, upd)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    untouched = np.setdiff1d(np.arange(rows), idx[inside])
    np.testing.assert_array_equal(got[untouched], logical[untouched])


class TestScatterAddRows:
    """Pallas RMW scatter kernel family (interpret mode on CPU) vs the
    tbl.at[idx].add oracle — covers the sort+segment dedup, the distinct-
    row precondition, wide (k chunks), narrow (rolled sub-tile), and
    packed-view paths."""

    def _check(self, rows, dim, n, seed=0, dup=True):
        idx = np.random.RandomState(seed + 1).randint(0, rows, (n,))
        if dup and n >= 8:
            idx[:8] = idx[0]   # heavy duplicates exercise the dedup
        _check_scatter_form(("add", dim), rows, idx, seed)

    def test_wide_multichunk(self):
        self._check(500, 256, 33)

    def test_lane_exact(self):
        self._check(1000, 128, 64)

    def test_narrow_rolled(self):
        self._check(1000, 64, 60)
        self._check(1000, 16, 80)

    def test_all_same_row(self):
        from dlrm_flexflow_tpu.ops.pallas.embedding_kernel import \
            scatter_add_rows
        tbl = np.zeros((64, 128), np.float32)
        idx = np.full((24,), 7, np.int32)
        upd = np.ones((24, 128), np.float32)
        got = np.asarray(scatter_add_rows(
            jnp.asarray(tbl), jnp.asarray(idx), jnp.asarray(upd),
            interpret=True))
        assert got[7].min() == got[7].max() == 24.0
        assert np.abs(np.delete(got, 7, axis=0)).max() == 0.0

    def test_packed_view(self):
        idx = np.random.RandomState(3).randint(0, 512, (40,))
        idx[:4] = idx[0]
        _check_scatter_form(("add_packed", 16), 512, idx, seed=3)


class TestScatterBlocks:
    """The block pipeline both scatter kernels share, every form: slot
    counts around `_scatter_block`, the count of valid slots that stands in
    for a predicate a row, ids outside the table."""

    ROWS = 4096

    @pytest.mark.parametrize("form", SCATTER_FORMS, ids=str)
    @pytest.mark.parametrize("blocks,extra", [(1, -1), (1, 0), (1, 1),
                                              (2, 3)])
    def test_slot_counts_around_a_block(self, form, blocks, extra):
        """B-1, B, B+1 and 2B+3 lookups: one block not full, one full,
        a second block of one slot, three blocks (every landing buffer,
        the reads started a step ahead, the writes waited two steps on)."""
        n = blocks * embedding_kernel._SCATTER_ROWS + extra
        idx = np.random.RandomState(n).randint(0, self.ROWS, (n,))
        idx[:8] = idx[0]
        _check_scatter_form(form, self.ROWS, idx)

    @pytest.mark.parametrize("form", SCATTER_FORMS, ids=str)
    def test_one_valid_slot_and_the_rest_pads(self, form):
        idx = np.full((300,), -1)
        idx[123] = 77
        _check_scatter_form(form, self.ROWS, idx)

    @pytest.mark.parametrize("form", SCATTER_FORMS, ids=str)
    def test_every_slot_the_same_row(self, form):
        _check_scatter_form(form, self.ROWS, np.full((300,), 1234))

    @pytest.mark.parametrize("form", SCATTER_FORMS, ids=str)
    def test_ids_outside_the_table_are_dropped(self, form):
        """The kernels run without Mosaic's bounds checks: an id below 0
        or at or past `rows` must start no DMA, wherever it stands among
        the lookups, and the rows at the table's two ends stay as they
        were unless a lookup names them."""
        rows = self.ROWS
        idx = np.random.RandomState(5).randint(0, rows, (300,))
        idx[::7] = rows + idx[::7] % 50           # at and past the end
        idx[3::11] = -1 - idx[3::11] % 50         # below 0
        idx[5], idx[6], idx[9] = rows, -1, -rows  # next to both ends
        idx[10], idx[12] = 1, rows - 2            # and inside, beside them
        _check_scatter_form(form, rows, idx)

    @pytest.mark.parametrize("form", SCATTER_FORMS, ids=str)
    def test_every_id_outside_the_table(self, form):
        idx = np.concatenate([np.arange(-40, 0), self.ROWS + np.arange(40)])
        _check_scatter_form(form, self.ROWS, idx)

    @pytest.mark.parametrize("rmw", [True, False], ids=["add", "write"])
    @pytest.mark.parametrize("n", [40, 255, 515])
    def test_one_operation_a_distinct_row_is_bit_exact(self, rmw, n):
        """After the dedup a row is touched once: the kernel's result is
        `.at[target].add(summed)` (`.set` for the write-only form) bit
        for bit, and the valid targets are a prefix of `target`."""
        rng = np.random.RandomState(n)
        view = rng.randn(600, 128).astype(np.float32)
        tile_rows = rng.randint(-5, 605, (n,)).astype(np.int32)
        tile_upds = rng.randn(n, 128).astype(np.float32)
        target, summed, _, m = embedding_kernel._dedup_tile_updates(
            jnp.asarray(tile_rows), jnp.asarray(tile_upds), interpret=True)
        assert m % embedding_kernel._scatter_block(n) == 0
        count = int(embedding_kernel._valid_prefix(target, 600)[0])
        inside = np.unique(tile_rows[(tile_rows >= 0) & (tile_rows < 600)])
        np.testing.assert_array_equal(np.asarray(target)[:count], inside)
        got = _scatter_tiles(rmw)(jnp.asarray(view), target, summed, True)
        at = jnp.asarray(view).at[target[:count]]
        want = at.add(summed[:count]) if rmw else at.set(summed[:count])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("rmw", [True, False], ids=["add", "write"])
    @pytest.mark.parametrize("m,count", [(16, 16), (64, 37), (64, 0),
                                         (80, 33), (160, 150), (8, 3)])
    def test_pipeline_has_no_race_and_leaves_no_dma(self, monkeypatch, rmw,
                                                    m, count):
        """Under the TPU interpreter, which keeps the DMAs asynchronous,
        counts the semaphores and starts a copy as late as its wait
        allows: blocks of 16 so that a few hundred rows cross every
        buffer, the boundary block and blocks past the count."""
        from jax._src.pallas.mosaic.interpret import \
            interpret_pallas_call as interpreter
        from jax.experimental.pallas import tpu as pltpu
        monkeypatch.setattr(embedding_kernel, "_SCATTER_ROWS", 16)
        rng = np.random.RandomState(m + count)
        view = rng.rand(400, 128).astype(np.float32)
        target = rng.permutation(400)[:m].astype(np.int32)
        target[count:] = -1
        tiles = rng.rand(m, 128).astype(np.float32)
        want = view.copy()
        if rmw:
            want[target[:count]] += tiles[:count]
        else:
            want[target[:count]] = tiles[:count]
        got = _scatter_tiles(rmw)(
            jnp.asarray(view), jnp.asarray(target), jnp.asarray(tiles),
            pltpu.InterpretParams(detect_races=True,
                                  dma_execution_mode="on_wait"))
        np.testing.assert_array_equal(np.asarray(got), want)
        assert not interpreter.races.races_found

    def test_targets_that_are_no_whole_blocks_are_refused(self):
        view = jnp.zeros((64, 128), jnp.float32)
        with pytest.raises(ValueError, match="multiple of the block"):
            embedding_kernel.scatter_write_tiles(
                view, jnp.zeros((300,), jnp.int32),
                jnp.zeros((300, 128), jnp.float32), interpret=True)

    @pytest.mark.parametrize("rmw", [True, False], ids=["add", "write"])
    def test_a_valid_target_behind_an_invalid_one_is_refused(self, rmw):
        """The count drops every slot after the first pad or outside id,
        and on the chip nothing checks: the interpreter tells a caller
        whose valid targets are no prefix, instead of losing updates."""
        view = jnp.zeros((64, 128), jnp.float32)
        tiles = jnp.ones((16, 128), jnp.float32)
        for hole in (-1, 64):
            target = np.arange(16, dtype=np.int32)
            target[5] = hole
            with pytest.raises(Exception, match="stand behind"):
                jax.block_until_ready(_scatter_tiles(rmw)(
                    view, jnp.asarray(target), tiles, True))

    def test_scatter_block_follows_the_slot_count(self):
        """The benchmark's three slot counts are whole blocks (the dedup
        pads none of them), a small call is one block of its own size,
        and the landing buffers stay a small part of VMEM."""
        block = embedding_kernel._scatter_block
        for m in (89856, 65536, 3328):
            assert block(m) == embedding_kernel._SCATTER_ROWS
            assert m % block(m) == 0
        assert block(40) == 40 and block(33) == 40 and block(1) == 8
        assert (embedding_kernel._SCATTER_SLOTS * block(10**6) * 512
                <= 2**20)


def _dedup_cases():
    """name -> (m,) tile rows. Blocks are `_RUN_ROWS` = `_SCATTER_ROWS` =
    256 sorted slots; ids outside a view are ids like any other to the
    dedup (only the scatter knows the view)."""
    rng = np.random.RandomState(29)
    b = embedding_kernel._RUN_ROWS
    return {
        "all_distinct": rng.permutation(3 * b + 40),
        "all_equal": np.full((2 * b + 9,), 77),
        "run_of_5000_beside_singletons": rng.permutation(np.concatenate(
            [np.arange(100), np.full((5000,), 100), 101 + np.arange(150)])),
        # sorted: 0 x 256 | 1 x 128, 2 x 128 | 3 x 512 | 4..: every run
        # ends where a block does, and one covers two whole blocks
        "runs_ending_on_block_boundaries": rng.permutation(np.repeat(
            np.arange(4 + b), [b, b // 2, b // 2, 2 * b] + [1] * b)),
        "every_id_outside_the_view": np.concatenate(
            [-1 - rng.randint(0, 30, (200,)), 2**30 + rng.randint(0, 30, (200,)),
             np.full((150,), -1)]),
        "under_one_block": rng.randint(0, 20, (37,)),
        "no_block_multiple": rng.randint(-3, 400, (2 * b + 77,)),
        "zipf_hot_ids": np.minimum(rng.zipf(1.05, (5 * b,)), 10**6),
    }


class TestDedupTileUpdates:
    """`_dedup_tile_updates`' contract against a float64 numpy reference:
    distinct targets ascending (read as unsigned) in front and -1 behind
    them, float32 run sums, a representative position a run, padded to a
    `_scatter_block` multiple."""

    @pytest.mark.parametrize("width", [128, 256])
    @pytest.mark.parametrize("case", sorted(_dedup_cases()))
    def test_contract(self, case, width):
        tile_rows = _dedup_cases()[case].astype(np.int32)
        m = len(tile_rows)
        upds = np.random.RandomState(m).randn(m, width).astype(np.float32)
        target, summed, rep, padded = embedding_kernel._dedup_tile_updates(
            jnp.asarray(tile_rows), jnp.asarray(upds), interpret=True)
        target, summed, rep = map(np.asarray, (target, summed, rep))
        assert padded % embedding_kernel._scatter_block(m) == 0
        assert padded - m < embedding_kernel._scatter_block(m)
        assert target.shape == rep.shape == (padded,)
        assert summed.shape == (padded, width) and summed.dtype == np.float32

        want = np.unique(tile_rows.astype(np.uint32)).astype(np.int32)
        runs = len(want)
        np.testing.assert_array_equal(target[:runs], want)
        np.testing.assert_array_equal(target[runs:], -1)
        member = tile_rows[None, :] == want[:, None]            # (runs, m)
        sums = member.astype(np.float64) @ upds.astype(np.float64)
        # float32 accumulation in any order: a few ulps of the sum of sizes
        room = 4 * np.finfo(np.float32).eps * (
            member.astype(np.float64) @ np.abs(upds).astype(np.float64))
        assert np.all(np.abs(summed[:runs] - sums) <= room)
        np.testing.assert_array_equal(summed[runs:], 0.0)
        assert np.all((rep >= 0) & (rep < m))
        # a pad's (-1) representative is read by no one: any position
        named = want != -1
        np.testing.assert_array_equal(tile_rows[rep[:runs]][named],
                                      want[named])

    @pytest.mark.parametrize("width", [128, 256])
    def test_sums_keep_float32_bits_a_bf16_product_would_drop(self, width):
        """Eight updates of 1 + 2**-20 sum to 8 + 2**-17 exactly in float32
        in any order; a one-hot product that rounds the updates to bfloat16
        gives 8."""
        rng = np.random.RandomState(8)
        tile_rows = rng.permutation(np.concatenate(
            [np.full((8,), 5), 10 + np.arange(292)])).astype(np.int32)
        upds = rng.randn(300, width).astype(np.float32)
        upds[tile_rows == 5] = 1.0 + 2.0 ** -20
        target, summed, _, _ = embedding_kernel._dedup_tile_updates(
            jnp.asarray(tile_rows), jnp.asarray(upds), interpret=True)
        assert int(target[0]) == 5
        np.testing.assert_array_equal(np.asarray(summed[0]),
                                      np.float32(8.0 + 2.0 ** -17))
        # a run of one is its update, bit for bit
        at = {int(t): s for s, t in enumerate(np.asarray(target)[:293])}
        for pos in (3, 100, 299):
            if tile_rows[pos] != 5:
                np.testing.assert_array_equal(
                    np.asarray(summed[at[int(tile_rows[pos])]]), upds[pos])

    @pytest.mark.parametrize("width", [128, 256])
    @pytest.mark.parametrize("case", ["all_distinct", "all_equal",
                                      "crossing_runs", "ragged"])
    def test_run_sum_pipeline_has_no_race_and_leaves_no_dma(
            self, monkeypatch, case, width):
        """`emb_run_sum` under the TPU interpreter (asynchronous DMAs,
        counted semaphores, a copy started as late as its wait allows), in
        blocks of 128: the fetches a step ahead, the ordered writes that
        overlap, the zero blocks of the tail."""
        from jax._src.pallas.mosaic.interpret import \
            interpret_pallas_call as interpreter
        from jax.experimental.pallas import tpu as pltpu
        monkeypatch.setattr(embedding_kernel, "_RUN_ROWS", 128)
        rng = np.random.RandomState(len(case))
        tile_rows = {
            "all_distinct": rng.permutation(512),
            "all_equal": np.full((512,), 3),
            "crossing_runs": np.repeat(np.arange(6),
                                       [100, 60, 300, 1, 50, 129]),
            "ragged": rng.randint(0, 150, (333,)),
        }[case].astype(np.int32)
        m = len(tile_rows)
        upds = rng.randn(m, width).astype(np.float32)
        target, summed, _, _ = embedding_kernel._dedup_tile_updates(
            jnp.asarray(tile_rows), jnp.asarray(upds),
            interpret=pltpu.InterpretParams(detect_races=True,
                                            dma_execution_mode="on_wait"))
        want = np.unique(tile_rows)
        member = (tile_rows[None, :] == want[:, None]).astype(np.float64)
        np.testing.assert_array_equal(np.asarray(target)[:len(want)], want)
        np.testing.assert_allclose(np.asarray(summed)[:len(want)],
                                   member @ upds.astype(np.float64),
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(summed)[len(want):], 0.0)
        assert not interpreter.races.races_found


class TestShardedScatter:
    """Multi-chip scatter (shard_map + local RMW kernel, interpret mode):
    row-block-sharded packed table, replicated indices/updates — each
    shard applies only its block's updates; result equals the dense
    oracle."""

    def _run(self, rows, d, n, axes_count=3, seed=0):
        import numpy as np

        import jax
        import jax.numpy as jnp
        from dlrm_flexflow_tpu.ops.pallas.embedding_kernel import \
            sharded_scatter_add_packed
        from dlrm_flexflow_tpu.parallel.mesh import make_mesh
        mesh = make_mesh(num_devices=8)
        row_axes = tuple(mesh.axis_names)      # 8-way row sharding
        rng = np.random.RandomState(seed)
        logical = rng.rand(rows, d).astype(np.float32)
        idx = rng.randint(0, rows, (n,)).astype(np.int32)
        idx[:6] = idx[0]                       # duplicates
        upd = rng.rand(n, d).astype(np.float32)
        want = logical.copy()
        np.add.at(want, idx, upd)
        r = 128 // d
        view = logical.reshape(rows // r, r * d)
        got = jax.jit(lambda v, i, u: sharded_scatter_add_packed(
            mesh, row_axes, v, i, u, d, interpret=True))(
                jnp.asarray(view), jnp.asarray(idx), jnp.asarray(upd))
        np.testing.assert_allclose(
            np.asarray(got).reshape(rows, d), want, rtol=1e-5, atol=1e-5)

    def test_narrow_rows(self):
        self._run(rows=1024, d=16, n=96)

    def test_half_tile_rows(self):
        self._run(rows=512, d=64, n=64)

    def test_full_tile_rows(self):
        self._run(rows=256, d=128, n=40)


class TestScatterWritePacked:
    """Write-only scatter (scatter_write_rows_packed): given the forward-
    gathered tiles, new rows land WITHOUT the RMW read; must equal the
    RMW scatter_add result exactly (duplicates summed)."""

    def _run(self, rows, d, n, seed=3):
        idx = np.random.RandomState(seed + 1).randint(0, rows, (n,))
        idx[:5] = idx[0]                       # duplicates
        _check_scatter_form(("write_packed", d), rows, idx, seed)

    def test_narrow_rows(self):
        self._run(rows=1024, d=16, n=96)

    def test_half_tile_rows(self):
        self._run(rows=512, d=64, n=64)

    def test_duplicates_across_tile_halves(self):
        # two different unpacked rows sharing one 128-lane tile must both
        # land (their rolled updates sum into one tile write)
        import jax
        import jax.numpy as jnp
        import numpy as np

        from dlrm_flexflow_tpu.ops.pallas.embedding_kernel import (
            scatter_write_rows_packed)
        rows, d = 64, 64
        logical = np.arange(rows * d, dtype=np.float32).reshape(rows, d)
        idx = np.asarray([10, 11, 11, 3], np.int32)    # 10,11 share tile 5
        upd = np.ones((4, d), np.float32)
        want = logical.copy()
        np.add.at(want, idx, upd)
        view = logical.reshape(rows // 2, 128)
        fwd_tiles = view[idx // 2]
        got = jax.jit(lambda v, i, u, t: scatter_write_rows_packed(
            v, i, u, t, d, interpret=True))(
                jnp.asarray(view), jnp.asarray(idx), jnp.asarray(upd),
                jnp.asarray(fwd_tiles))
        np.testing.assert_allclose(np.asarray(got).reshape(rows, d), want,
                                   rtol=0, atol=0)


class TestStatefulTilesPacked:
    """The lane-packed tile path of the stateful sparse update must agree
    with the logical-row XLA path (its oracle) — including the per-lane
    touched masks that keep a tile's OTHER logical rows' state undecayed."""

    def _run(self, opt, rows=256, d=16, n=96, fwd=False, seed=0,
             past_end=0):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from dlrm_flexflow_tpu.ops.embedding import (
            _stateful_update_rows_xla, _stateful_update_tiles_packed)
        rng = np.random.RandomState(seed)
        logical = rng.randn(rows, d).astype(np.float32)
        gidx = rng.randint(0, rows, size=(n,)).astype(np.int32)
        # lookups at and past the table's end: both paths drop them
        gidx[:past_end] = rows + np.arange(past_end) * 3
        upd = rng.randn(n, d).astype(np.float32)
        slabs = {k: rng.rand(rows, d).astype(np.float32)
                 for k in opt.sparse_slab_names()}
        step = jnp.asarray(3, jnp.int32)

        want_w, want_s = jax.jit(
            lambda l, g, u, s: _stateful_update_rows_xla(
                l, g, u, opt, s, step))(
                    jnp.asarray(logical), jnp.asarray(gidx),
                    jnp.asarray(upd), {k: jnp.asarray(v)
                                       for k, v in slabs.items()})

        r = 128 // d
        view = logical.reshape(rows // r, r * d)
        slab_views = {k: v.reshape(rows // r, r * d)
                      for k, v in slabs.items()}
        fwd_tiles = (jnp.asarray(view[np.minimum(gidx // r, rows // r - 1)])
                     if fwd else None)
        got_w, got_s = jax.jit(
            lambda v, g, u, s: _stateful_update_tiles_packed(
                v, g, u, d, opt, s, step, fwd_tiles=fwd_tiles,
                interpret=True))(
                    jnp.asarray(view), jnp.asarray(gidx),
                    jnp.asarray(upd), {k: jnp.asarray(v)
                                       for k, v in slab_views.items()})
        np.testing.assert_allclose(
            np.asarray(got_w).reshape(rows, d), np.asarray(want_w),
            rtol=1e-5, atol=1e-6)
        for k in slabs:
            np.testing.assert_allclose(
                np.asarray(got_s[k]).reshape(rows, d),
                np.asarray(want_s[k]), rtol=1e-5, atol=1e-6, err_msg=k)

    def test_momentum(self):
        import dlrm_flexflow_tpu as ff
        self._run(ff.SGDOptimizer(lr=0.1, momentum=0.9))

    def test_momentum_wd_nesterov(self):
        import dlrm_flexflow_tpu as ff
        self._run(ff.SGDOptimizer(lr=0.1, momentum=0.9, nesterov=True,
                                  weight_decay=1e-3))

    def test_adam(self):
        import dlrm_flexflow_tpu as ff
        self._run(ff.AdamOptimizer(alpha=0.01))

    def test_adam_with_fwd_residuals(self):
        import dlrm_flexflow_tpu as ff
        self._run(ff.AdamOptimizer(alpha=0.01), fwd=True)

    def test_adam_full_tile_rows(self):
        import dlrm_flexflow_tpu as ff
        self._run(ff.AdamOptimizer(alpha=0.01), rows=128, d=128, n=64)

    @pytest.mark.parametrize("blocks,extra", [(1, -1), (1, 1), (2, 3)])
    @pytest.mark.parametrize("fwd", [False, True])
    def test_adam_slot_counts_around_a_block(self, blocks, extra, fwd):
        """The three write-only scatters of a step (weight, m, v) at
        B-1, B+1 and 2B+3 lookups, some of them past the table's end."""
        import dlrm_flexflow_tpu as ff
        n = blocks * embedding_kernel._SCATTER_ROWS + extra
        self._run(ff.AdamOptimizer(alpha=0.01), rows=8192, d=64, n=n,
                  fwd=fwd, past_end=5)

    def test_three_writes_share_one_target(self, monkeypatch):
        """Weight and both Adam slabs land through scatter_write_tiles on
        the one deduped `target`: valid rows first, each once, and a
        `_scatter_block` multiple long."""
        import dlrm_flexflow_tpu as ff
        seen = []
        real = embedding_kernel.scatter_write_tiles

        def spy(view, target, vals, interpret=False):
            seen.append((view.shape, np.asarray(target)))
            return real(view, target, vals, interpret=interpret)

        monkeypatch.setattr(embedding_kernel, "scatter_write_tiles", spy)
        with jax.disable_jit():
            self._run(ff.AdamOptimizer(alpha=0.01), rows=512, d=64, n=70,
                      past_end=4)
        assert len(seen) == 3
        vrows = seen[0][0][0]
        for shape, target in seen:
            assert shape == (vrows, 128)
            np.testing.assert_array_equal(target, seen[0][1])
        target = seen[0][1]
        assert len(target) % embedding_kernel._scatter_block(70) == 0
        count = int(embedding_kernel._valid_prefix(jnp.asarray(target),
                                                   vrows)[0])
        inside = target[:count]
        assert np.all(np.diff(inside) > 0) and inside[-1] < vrows
        assert not np.any((target[count:] >= 0) & (target[count:] < vrows))
