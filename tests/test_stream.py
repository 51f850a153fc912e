"""Streaming DF_LF runtime: recompile-free hot path + capacity ladder.

Covers the acceptance matrix of the streaming work: zero retraces of the
fused driver across a multi-batch stream, stream results matching the
from-scratch rebuild path on insertion+deletion batches, and the
capacity-padded ``apply_delta`` edge cases (emptied tiles stay inert,
bucket-overflow growth rewidens correctly, grid changes are rejected).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import pagerank as pr
from repro.core import frontier as fr
from repro.core import pallas_engine as pe
from repro.core.delta import random_batch
from repro.core.graph import HostGraph
from repro.core.incremental import IncrementalPullMatrix, MatrixAux
from repro.core.stream import StreamRunner, run_stream
from repro.graphs.generators import rmat, grid_road
from repro.kernels.block_spmv import ops


# ---------------------------------------------------------------------------
# apply_delta edge cases (capacity ladder semantics)
# ---------------------------------------------------------------------------

def _rand_mat(n=300, m=2000, block=64, seed=0, padded=True):
    rng = np.random.default_rng(seed)
    rows, cols = rng.integers(0, n, m), rng.integers(0, n, m)
    mat = ops.build_block_sparse(rows, cols, n, n, block=block,
                                 dtype=np.float64, padded=padded)
    return mat, rows, cols, rng


class TestApplyDeltaEdgeCases:
    def test_deletion_emptied_tiles_stay_inert(self):
        """Deleting every edge of a tile leaves an all-zero tile that is
        still referenced (structure is monotone) but contributes nothing."""
        mat, rows, cols, rng = _rand_mat()
        B = mat.block
        # empty the (0, 0) tile completely
        in_tile = (rows // B == 0) & (cols // B == 0)
        assert in_tile.sum() > 0
        mat1 = ops.apply_delta(mat, rows[in_tile], cols[in_tile],
                               -np.ones(int(in_tile.sum())))
        # slot tables unchanged: the emptied tile is still present
        assert jnp.array_equal(mat1.tile_cols, mat.tile_cols)
        assert mat1.tiles.shape == mat.tiles.shape
        x = jnp.asarray(rng.random(mat.n_cols))
        y = ops.block_spmv(mat1, x, backend="xla")
        keep = ~in_tile
        fresh = ops.build_block_sparse(rows[keep], cols[keep], mat.n_rows,
                                       mat.n_cols, block=B, dtype=np.float64)
        assert pr.linf(y, ops.block_spmv(fresh, x, backend="xla")) < 1e-12

    def test_growth_past_capacity_bucket_rewidens(self):
        """Adding more tiles than the preallocated pool / slot bucket grows
        both to the next bucket and stays numerically exact."""
        n, B = 256, 32
        rows0 = np.arange(0, n, B)          # one diagonal tile per row-block
        mat = ops.build_block_sparse(rows0, rows0, n, n, block=B,
                                     dtype=np.float64, padded=True)
        cap0, mt0 = mat.tile_capacity, mat.max_tiles
        # flood row-block 0 with a tile in every column-block → must exceed
        # the slot bucket; enough distinct tiles to overflow the pool too
        rr, cc = np.meshgrid(np.arange(0, n, B), np.arange(0, n, B))
        dr, dc = rr.reshape(-1), cc.reshape(-1)
        mat1 = ops.apply_delta(mat, dr, dc, np.ones(len(dr)))
        assert mat1.max_tiles > mt0
        assert mat1.tile_capacity >= mat1.n_tiles()
        assert mat1.tile_capacity > cap0
        # buckets stay on the doubling ladder
        assert mat1.tile_capacity == ops.capacity_bucket(mat1.tile_capacity)
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.random(n))
        fresh = ops.build_block_sparse(
            np.concatenate([rows0, dr]), np.concatenate([rows0, dc]), n, n,
            block=B, dtype=np.float64,
            values=np.ones(len(rows0) + len(dr)))
        assert pr.linf(ops.block_spmv(mat1, x, backend="xla"),
                       ops.block_spmv(fresh, x, backend="xla")) < 1e-12

    def test_within_bucket_growth_keeps_shapes(self):
        """New tiles inside the preallocated capacity leave tiles.shape and
        max_tiles untouched — the recompile-free invariant."""
        mat, rows, cols, rng = _rand_mat(m=40)  # block-sparse structure
        free = mat.tile_capacity - mat.n_tiles()
        assert free > 0, "padded build must leave headroom"
        # one new tile in an existing row (slot headroom from the ladder)
        occ = np.asarray(mat.tile_cols)
        rb = int(np.argmin((occ >= 0).sum(1)))
        cb_free = int(np.where(~np.isin(np.arange(mat.n_cb),
                                        occ[rb][occ[rb] >= 0]))[0][0])
        mat1 = ops.apply_delta(mat, np.array([rb * mat.block]),
                               np.array([cb_free * mat.block]), np.ones(1))
        assert mat1.tiles.shape == mat.tiles.shape
        assert mat1.max_tiles == mat.max_tiles

    def test_grid_size_change_rejected(self):
        mat, _, _, _ = _rand_mat(n=300)
        with pytest.raises(ValueError, match="grid"):
            ops.apply_delta(mat, np.array([mat.n_rows]), np.array([0]),
                            np.ones(1))
        with pytest.raises(ValueError, match="grid"):
            ops.apply_delta(mat, np.array([0]), np.array([-1]), np.ones(1))
        hg = grid_road(16, seed=0)
        g_small = hg.snapshot(block_size=64)
        inc = IncrementalPullMatrix.from_snapshot(g_small)
        g_big = grid_road(48, seed=0).snapshot(block_size=64)
        with pytest.raises(ValueError, match="rebuild"):
            inc.advance(hg, g_big, np.zeros((0, 2)), np.zeros((0, 2)))


# ---------------------------------------------------------------------------
# cached MatrixAux (block_adjacency + rb_in/rb_out maintained per delta)
# ---------------------------------------------------------------------------

def test_matrix_aux_tracks_fresh_recompute():
    hg = rmat(9, avg_degree=6, seed=5)
    g = hg.snapshot(block_size=64)
    inc = IncrementalPullMatrix.from_snapshot(g)
    cur = hg
    for i in range(3):
        dels, ins = random_batch(cur, 1e-2, seed=20 + i)
        nxt = cur.apply_batch(dels, ins)
        g_new = nxt.snapshot(block_size=64)
        inc.advance(cur, g_new, dels, ins)
        cur = nxt
    fresh = MatrixAux.from_parts(inc.mat, cur.snapshot(block_size=64))
    np.testing.assert_array_equal(inc.aux.rb_in, fresh.rb_in)
    np.testing.assert_array_equal(inc.aux.rb_out, fresh.rb_out)
    # cached presence is monotone ⊇ the recomputed one and covers it
    assert bool(np.all(inc.aux.bmat >= fresh.bmat))
    res = pr.df_pagerank(
        cur.snapshot(block_size=64), cur.snapshot(block_size=64),
        fr.batch_to_device(cur.snapshot(block_size=64), np.zeros((0, 2)),
                           np.zeros((0, 2))),
        jnp.asarray(pr.numpy_reference(cur.snapshot(block_size=64),
                                       iterations=300)),
        mode="lf", engine="pallas", pallas_mat=inc.mat, pallas_aux=inc.aux)
    assert res.converged


def _aux_on_lattice():
    """MatrixAux of a 16 x 16 block grid whose presence map is banded, so
    a batch can reach cells that are still off."""
    g = grid_road(32, seed=0).snapshot(block_size=64)
    return IncrementalPullMatrix.from_snapshot(g).aux


def test_matrix_aux_patches_bmat_in_place():
    """The presence map is patched in its own buffer, and exactly the
    batch's (dst-block, src-block) cells turn on — inserted and deleted
    coordinates alike, since presence is monotone."""
    aux = _aux_on_lattice()
    buf = aux.bmat
    before = buf.copy()
    off = np.argwhere(~before)[[0, -1]]
    on = np.argwhere(before)[0]
    rb = np.array([off[0, 0], off[1, 0], on[0]])
    cb = np.array([off[0, 1], off[1, 1], on[1]])
    rows, cols = rb * 64 + 3, cb * 64 + 5
    aux.apply_delta(64, rows, cols, np.array([1.0, 1.0, -1.0]))
    assert aux.bmat is buf and np.shares_memory(aux.bmat, buf)
    want = before.copy()
    want[rb, cb] = True
    np.testing.assert_array_equal(aux.bmat, want)
    assert int(aux.bmat.sum()) == int(before.sum()) + 2


def test_matrix_aux_empty_batch_leaves_bmat():
    aux = _aux_on_lattice()
    buf = aux.bmat
    before = buf.copy()
    none = np.zeros(0, np.int64)
    aux.apply_delta(64, none, none, np.zeros(0))
    assert aux.bmat is buf
    np.testing.assert_array_equal(aux.bmat, before)


# ---------------------------------------------------------------------------
# streaming runtime
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def stream_setup():
    hg = rmat(9, avg_degree=6, seed=3)
    g = hg.snapshot(block_size=64)
    r0 = jnp.asarray(pr.numpy_reference(g, iterations=300))
    batches = []
    cur = hg
    for i in range(4):
        dels, ins = random_batch(cur, 5e-3, seed=100 + i)
        batches.append((dels, ins))
        cur = cur.apply_batch(dels, ins)
    return hg, g, r0, batches


def test_zero_retraces_across_stream(stream_setup):
    """≥3-batch stream: after the warmup batch the fused driver must not
    retrace — the capacity-padded matrix and the snapshot-free operand set
    keep every jit cache key stable."""
    hg, g, r0, batches = stream_setup
    runner = StreamRunner(hg, block_size=64, r0=r0)
    sizes = []
    for dels, ins in batches:
        sizes.append(runner.step(dels, ins).driver_cache_size)
    assert len(sizes) >= 3
    assert sizes[0] >= 0, "jit cache stats unavailable"
    assert sizes[-1] == sizes[0], f"driver retraced during stream: {sizes}"
    # run_stream's aggregate agrees
    rep = run_stream(hg, batches, block_size=64, r0=r0)
    assert rep.retraces_post_warmup == 0


def test_stream_matches_from_scratch_rebuild(stream_setup):
    """Streaming results must match the rebuild-everything path on
    insertion+deletion batches (same engine, same hyperparameters)."""
    hg, g, r0, batches = stream_setup
    runner = StreamRunner(hg, block_size=64, r0=r0)
    cur, r_ref = hg, r0
    for dels, ins in batches:
        res = runner.step(dels, ins)
        g_prev = cur.snapshot(block_size=64)
        cur = cur.apply_batch(dels, ins)
        g_new = cur.snapshot(block_size=64)
        oracle = pr.df_pagerank(
            g_prev, g_new, fr.batch_to_device(g_new, dels, ins), r_ref,
            mode="lf", engine="pallas")
        r_ref = oracle.ranks
        assert res.stats.converged
        assert pr.linf(res.ranks, oracle.ranks) < 1e-12
    # and against the independent oracle on the final graph
    ref = pr.numpy_reference(cur.snapshot(block_size=64), iterations=300)
    assert pr.linf(runner.R[:cur.n], jnp.asarray(ref[:cur.n])) < 1e-9


def test_stream_seed_matches_initial_affected(stream_setup):
    """The tile-matrix frontier seed equals the snapshot-based marking of
    paper Alg. 1 lines 4-6."""
    from repro.core.stream import _seed_affected
    hg, g, r0, batches = stream_setup
    runner = StreamRunner(hg, block_size=64, r0=r0)
    cur = hg
    for dels, ins in batches[:2]:
        mat_prev = runner.inc.mat
        g_prev = cur.snapshot(block_size=64)
        res = runner.step(dels, ins)  # noqa: F841 (advances runner state)
        cur = cur.apply_batch(dels, ins)
        g_new = cur.snapshot(block_size=64)
        batch = fr.batch_to_device(g_new, dels, ins)
        want = fr.initial_affected(g_prev, g_new, batch)
        got = _seed_affected(
            mat_prev, runner.inc.mat, jnp.asarray(runner.inc.aux.bmat),
            batch, runner.valid, block_size=64,
            interpret=runner.interpret, backend=runner.backend)
        assert bool(jnp.all(got == want))


def test_stream_device_mirrors_track_ground_truth(stream_setup):
    """The device-resident operand mirrors (out_deg / rb_in / rb_out /
    bmat), patched per batch by one O(batch) scatter, must equal the values
    a fresh snapshot of the final graph would produce."""
    hg, g, r0, batches = stream_setup
    runner = StreamRunner(hg, block_size=64, r0=r0)
    cur = hg
    for dels, ins in batches:
        runner.step(dels, ins)
        cur = cur.apply_batch(dels, ins)
    g_fin = cur.snapshot(block_size=64)
    np.testing.assert_array_equal(np.asarray(runner._out_deg),
                                  np.asarray(g_fin.out_deg))
    np.testing.assert_array_equal(np.asarray(runner._rb_in),
                                  np.asarray(g_fin.block_in_edges()))
    np.testing.assert_array_equal(np.asarray(runner._rb_out),
                                  np.asarray(g_fin.block_out_edges()))
    # presence mirror: monotone superset covering the true structure, and
    # in sync with the numpy twin maintained by IncrementalPullMatrix
    fresh_bmat = np.asarray(ops.block_adjacency(
        pe.build_pull_matrix(g_fin)))
    got = np.asarray(runner._bmat)
    assert bool(np.all(got >= fresh_bmat))
    np.testing.assert_array_equal(got, runner.inc.aux.bmat)
    np.testing.assert_array_equal(np.asarray(runner._rb_in),
                                  runner.inc.aux.rb_in)
    np.testing.assert_array_equal(np.asarray(runner._rb_out),
                                  runner.inc.aux.rb_out)


def test_stream_rejects_unknown_mode():
    hg = rmat(8, avg_degree=4, seed=0)
    with pytest.raises(ValueError):
        StreamRunner(hg, mode="nope")
