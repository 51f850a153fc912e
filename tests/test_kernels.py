"""Tile-SpMV backends vs pure-jnp oracle.

Sweeps shapes, block sizes, densities and dtypes across both backends (the
Pallas kernels in interpret mode and the XLA gather/einsum tile path);
property tests assert the algebraic invariants the PageRank engines rely on
(linearity, OR-idempotence).  The property tests require ``hypothesis`` and
are skipped (not errored) where it is absent.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:        # container without hypothesis: skip, don't error
    HAVE_HYPOTHESIS = False

from repro.kernels.block_spmv.ops import (build_block_sparse, block_spmv,
                                          pagerank_pull_step,
                                          frontier_expand_op)
from repro.kernels.block_spmv.ref import spmv_ref, pagerank_pull_step_ref

BACKENDS = ["pallas", "xla"]


def _random_edges(n_rows, n_cols, m, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, n_rows, m), rng.integers(0, n_cols, m)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("n_rows,n_cols,m", [
    (17, 17, 40), (64, 64, 500), (130, 70, 900), (300, 300, 4000),
    (1000, 1000, 20000), (128, 512, 2000),
])
@pytest.mark.parametrize("block", [8, 32, 128])
def test_spmv_shapes_match_ref(n_rows, n_cols, m, block, backend):
    rows, cols = _random_edges(n_rows, n_cols, m, seed=n_rows + block)
    x = jnp.asarray(np.random.default_rng(1).random(n_cols), jnp.float32)
    mat = build_block_sparse(rows, cols, n_rows, n_cols, block=block)
    y = block_spmv(mat, x, interpret=True, backend=backend)
    yref = spmv_ref(rows, cols, n_rows, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_padded_build_matches_exact(backend):
    """Capacity-padded layout (streaming) computes the same product."""
    rows, cols = _random_edges(300, 300, 3000, seed=3)
    x = jnp.asarray(np.random.default_rng(3).random(300), jnp.float32)
    exact = build_block_sparse(rows, cols, 300, 300, block=64)
    padded = build_block_sparse(rows, cols, 300, 300, block=64, padded=True)
    assert padded.tiles.shape[0] >= exact.tiles.shape[0]
    assert padded.max_tiles >= exact.max_tiles
    np.testing.assert_allclose(
        np.asarray(block_spmv(padded, x, interpret=True, backend=backend)),
        np.asarray(block_spmv(exact, x, interpret=True, backend=backend)),
        rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)])
def test_spmv_dtypes(dtype, tol, backend):
    rows, cols = _random_edges(256, 256, 3000, seed=0)
    x = jnp.asarray(np.random.default_rng(2).random(256), dtype)
    mat = build_block_sparse(rows, cols, 256, 256, block=64,
                             dtype=np.float32)
    mat = mat.__class__(**{**mat.__dict__,
                           "tiles": mat.tiles.astype(dtype)})
    y = block_spmv(mat, x, interpret=True, backend=backend)
    yref = spmv_ref(rows, cols, 256, x.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yref), rtol=tol, atol=tol)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("block", [16, 64])
def test_or_semiring_matches_ref(block, backend):
    rows, cols = _random_edges(400, 400, 5000, seed=4)
    f = jnp.asarray(np.random.default_rng(5).random(400) < 0.1, jnp.float32)
    mat = build_block_sparse(rows, cols, 400, 400, block=block)
    y = block_spmv(mat, f, semiring="or", interpret=True, backend=backend)
    yref = spmv_ref(rows, cols, 400, f, semiring="or")
    assert bool(jnp.all(y == yref))


@pytest.mark.parametrize("backend", BACKENDS)
def test_or_semiring_weighted_is_normalized(backend):
    """OR output is a 0/1 indicator even for fractional matrix values, on
    both backends and on the active/bucketed variants (the Pallas active
    kernel once leaked raw tile values here)."""
    from repro.kernels.block_spmv.ops import (block_spmv_active,
                                              block_spmv_active_bucketed)
    rows, cols = _random_edges(200, 200, 1200, seed=12)
    vals = np.full(1200, 0.3, np.float32)
    mat = build_block_sparse(rows, cols, 200, 200, block=32, values=vals)
    f = jnp.asarray(np.random.default_rng(13).random(200) < 0.1, jnp.float32)
    y = block_spmv(mat, f, semiring="or", interpret=True, backend=backend)
    assert bool(jnp.all((y == 0) | (y == 1)))
    ids = jnp.arange(mat.n_rb, dtype=jnp.int32)
    ya = block_spmv_active(mat, f, ids, semiring="or", interpret=True,
                           backend=backend)
    assert bool(jnp.all(ya == y))
    yb = block_spmv_active_bucketed(mat, f, ids, jnp.asarray(mat.n_rb),
                                    semiring="or", interpret=True,
                                    backend=backend)
    assert bool(jnp.all(yb == y))


@pytest.mark.parametrize("backend", BACKENDS)
def test_weighted_values(backend):
    rows, cols = _random_edges(100, 100, 700, seed=6)
    vals = np.random.default_rng(7).random(700).astype(np.float32)
    x = jnp.asarray(np.random.default_rng(8).random(100), jnp.float32)
    mat = build_block_sparse(rows, cols, 100, 100, block=32, values=vals)
    y = block_spmv(mat, x, interpret=True, backend=backend)
    yref = spmv_ref(rows, cols, 100, x, values=vals)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yref), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_pagerank_pull_step_op(backend):
    rng = np.random.default_rng(9)
    n, m = 500, 4000
    src, dst = _random_edges(n, n, m, seed=9)
    # pull matrix A[v,u] = 1 for edge u→v → rows=dst, cols=src
    mat = build_block_sparse(dst, src, n, n, block=64)
    out_deg = np.maximum(np.bincount(src, minlength=n), 1)
    inv = jnp.asarray(1.0 / out_deg, jnp.float32)
    r = jnp.asarray(rng.random(n), jnp.float32)
    r = r / r.sum()
    y = pagerank_pull_step(mat, r, inv, n, interpret=True, backend=backend)
    yref = pagerank_pull_step_ref(dst, src, n, r, inv, n)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yref), rtol=2e-5,
                               atol=2e-6)


@pytest.mark.parametrize("semiring", ["sum", "or"])
def test_chunked_launches_match_xla_and_full_kernel(semiring):
    """With the SMEM prefetch budget forced tiny, every ladder bucket above
    four row-blocks splits into several launches; each bucket must still
    match the XLA active path and the full kernel, padded slots included."""
    from repro.kernels.block_spmv import block_spmv as bk
    from repro.kernels.block_spmv.ops import (_block_spmv_active_xla,
                                              active_ladder)
    n = 300
    rows, cols = _random_edges(n, n, 2500, seed=21)
    mat = build_block_sparse(rows, cols, n, n, block=8, padded=True)
    mt, B = mat.max_tiles, mat.block
    tiny = 32 * mt                       # launch_rows == 4
    assert bk.launch_rows(mt, tiny) == 4
    rng = np.random.default_rng(22)
    x = rng.random(mat.n_cb * B)
    if semiring == "or":
        x = x < 0.1
    x = jnp.asarray(x, jnp.float32)
    kw = dict(block=B, max_tiles=mt, semiring=semiring, interpret=True)
    args = (mat.tile_idx, mat.tile_cols, mat.tiles, x)
    full = np.asarray(bk.block_spmv_pallas(*args, **kw))
    np.testing.assert_array_equal(
        np.asarray(bk.block_spmv_pallas(*args, smem_budget=tiny, **kw)),
        full)
    act = np.sort(rng.permutation(mat.n_rb)[:mat.n_rb // 2 + 3])
    for K in active_ladder(mat.n_rb, base=4):
        take = act[:max(1, K - 3)]                    # leave padded slots
        ids = np.full(K, -1, np.int32)
        ids[:len(take)] = take
        ids = jnp.asarray(ids)
        y = np.asarray(bk.block_spmv_active_pallas(ids, *args,
                                                   smem_budget=tiny, **kw))
        ref = np.asarray(_block_spmv_active_xla(ids, *args, block=B,
                                                max_tiles=mt,
                                                semiring=semiring))
        on = np.repeat(np.isin(np.arange(mat.n_rb), take), B)
        np.testing.assert_allclose(y[on], ref[on], rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(y[on], full[on], rtol=1e-6, atol=1e-6)


def _rows_of_widths(widths, block, n_cb, seed):
    """Edges whose row-block i owns tiles in ``widths[i]`` distinct column
    blocks, a few random entries each; returns (rows, cols, values)."""
    rng = np.random.default_rng(seed)
    rows, cols = [], []
    for rb, w in enumerate(widths):
        for cb in rng.choice(n_cb, w, replace=False):
            k = int(rng.integers(1, 4))
            rows.append(rb * block + rng.integers(0, block, k))
            cols.append(cb * block + rng.integers(0, block, k))
    rows = np.concatenate(rows)
    cols = np.concatenate(cols)
    return rows, cols, rng.random(len(rows)).astype(np.float32)


# row-block widths: empty rows, rows full to max_tiles (6), mixed widths
WIDTHS = [0, 6, 3, 0, 1, 6, 2, 5, 0, 4, 6, 1]


@pytest.mark.parametrize("block", [8, 64])
@pytest.mark.parametrize("semiring", ["sum", "or"])
@pytest.mark.parametrize("case", ["widths", "ladder_pad", "ragged_step",
                                  "launches"])
def test_live_tile_walk_matches_xla_and_dense(case, semiring, block):
    """The kernel walks each row-block's live slots only: against the XLA
    tile path and a dense reference, with empty and full rows, -1 ladder
    padding, a launch whose K is no multiple of the rows per step, and
    several launches forced by a small SMEM budget."""
    from repro.kernels.block_spmv import block_spmv as bk
    from repro.kernels.block_spmv.ops import _block_spmv_active_xla
    n_rb = n_cb = len(WIDTHS)
    n = n_rb * block
    rows, cols, vals = _rows_of_widths(WIDTHS, block, n_cb, seed=block)
    mat = build_block_sparse(rows, cols, n, n, block=block, values=vals)
    mt = mat.max_tiles
    assert mt == max(WIDTHS)
    rng = np.random.default_rng(31)
    x = rng.random(n)
    if semiring == "or":
        x = x < 0.3
    x = jnp.asarray(x, jnp.float32)
    budget = bk.SMEM_PREFETCH_BUDGET
    if case == "widths":                  # every row-block, in order
        ids = np.arange(n_rb)
    elif case == "ladder_pad":            # a bucket of 16: 7 ids, 9 x -1
        ids = np.full(16, -1)
        ids[:7] = rng.permutation(n_rb)[:7]
    elif case == "ragged_step":           # K = 11: no multiple of 8 rows
        ids = rng.permutation(n_rb)[:11]
        assert 11 % bk.rows_per_step(mt, 11) != 0
    else:                                 # launches of 2 row-blocks
        ids = np.concatenate([rng.permutation(n_rb), [-1, -1, -1]])
        budget = 3 * 2 * mt * 4           # tables of 2 rows fit, of 4 not
        assert bk.launch_rows(mt, budget) == 2
    ids = jnp.asarray(ids, jnp.int32)
    args = (mat.tile_idx, mat.tile_cols, mat.tiles, x)
    y = np.asarray(bk.block_spmv_active_pallas(
        ids, *args, block=block, max_tiles=mt, semiring=semiring,
        interpret=True, smem_budget=budget))
    ref = np.asarray(_block_spmv_active_xla(ids, *args, block=block,
                                            max_tiles=mt, semiring=semiring))
    dense = np.zeros((n, n))
    np.add.at(dense, (rows, cols), vals.astype(np.float64))
    want = dense @ np.asarray(x, np.float64)
    if semiring == "or":
        want = (want > 0).astype(np.float64)
    act = np.asarray(ids)
    on = np.repeat(np.isin(np.arange(n_rb), act[act >= 0]), block)
    np.testing.assert_allclose(y[on], ref[on], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y[on], want[on], rtol=1e-5, atol=1e-6)


def test_live_slots_stay_a_prefix_across_deltas():
    """The kernel stops a row-block's walk at its first -1 slot, so the
    live slots of every row must stay a prefix of its slot row after
    insert and delete batches (new tiles, emptied tiles, a widened
    table), and the kernel must still match the XLA path."""
    from repro.kernels.block_spmv.ops import apply_delta
    n, B = 2048, 32
    rng = np.random.default_rng(41)
    rows, cols = _random_edges(n, n, 300, seed=42)
    mat = build_block_sparse(rows, cols, n, n, block=B, padded=True)
    live_edges = [(int(r), int(c)) for r, c in zip(rows, cols)]
    widths = []
    for step in range(6):
        ins_r, ins_c = _random_edges(n, n, 20 + 30 * (step % 2),
                                     seed=100 + step)
        if step == 3:                 # crowd one row-block past its width
            ins_r = np.concatenate([ins_r, np.full(40, 5 * B)])
            ins_c = np.concatenate([ins_c, np.arange(40) * B])
        pick = rng.choice(len(live_edges), 30, replace=False)
        del_r = np.array([live_edges[i][0] for i in pick])
        del_c = np.array([live_edges[i][1] for i in pick])
        live_edges = [e for i, e in enumerate(live_edges)
                      if i not in set(pick.tolist())]
        live_edges += list(zip(ins_r.tolist(), ins_c.tolist()))
        mat = apply_delta(mat, np.concatenate([ins_r, del_r]),
                          np.concatenate([ins_c, del_c]),
                          np.concatenate([np.ones(len(ins_r)),
                                          -np.ones(len(del_r))]))
        occ = np.asarray(mat.tile_cols) >= 0
        assert not (occ[:, 1:] & ~occ[:, :-1]).any(), f"hole after {step}"
        widths.append(mat.max_tiles)
    assert widths[-1] > widths[0]             # the table widened once
    x = jnp.asarray(rng.random(n), jnp.float32)
    r_all, c_all = (np.array(v) for v in zip(*live_edges))
    np.testing.assert_allclose(
        np.asarray(block_spmv(mat, x, interpret=True, backend="pallas")),
        np.asarray(spmv_ref(r_all, c_all, n, x)), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("entry", ["full", "active"])
def test_unknown_semiring_rejected(entry):
    """Only "sum" and "or" exist: any other name fails before a launch
    instead of running the OR walk."""
    from repro.kernels.block_spmv import block_spmv as bk
    rows, cols = _random_edges(64, 64, 100, seed=51)
    mat = build_block_sparse(rows, cols, 64, 64, block=8)
    args = (mat.tile_idx, mat.tile_cols, mat.tiles,
            jnp.ones(64, jnp.float32))
    kw = dict(block=8, max_tiles=mat.max_tiles, semiring="max",
              interpret=True)
    with pytest.raises(ValueError, match="max"):
        if entry == "full":
            bk.block_spmv_pallas(*args, **kw)
        else:
            bk.block_spmv_active_pallas(jnp.arange(4, dtype=jnp.int32),
                                        *args, **kw)


def test_frontier_expand_matches_engine_semantics():
    """OR kernel on the pull layout == out_neighbor_or on the snapshot."""
    from repro.core.graph import HostGraph, out_neighbor_or
    rng = np.random.default_rng(10)
    n = 256
    edges = np.stack([rng.integers(0, n, 1500),
                      rng.integers(0, n, 1500)], 1)
    hg = HostGraph(n, edges)
    g = hg.snapshot(block_size=64)
    src = np.asarray(g.src)[:g.m]
    dst = np.asarray(g.dst)[:g.m]
    mat = build_block_sparse(dst, src, n, n, block=64)
    flags = jnp.asarray(rng.random(n) < 0.07)
    for backend in BACKENDS:
        ours = frontier_expand_op(mat, flags, interpret=True,
                                  backend=backend) > 0
        theirs = out_neighbor_or(g, jnp.concatenate(
            [flags, jnp.zeros(g.n_pad - n, bool)]))[:n]
        assert bool(jnp.all(ours == theirs))


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(st.integers(2, 60), st.integers(1, 400),
           st.integers(0, 2 ** 31 - 1))
    def test_property_linearity(n, m, seed):
        """SpMV is linear: A(ax + by) == a·Ax + b·Ay."""
        rows, cols = _random_edges(n, n, m, seed=seed)
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.random(n), jnp.float32)
        y = jnp.asarray(rng.random(n), jnp.float32)
        mat = build_block_sparse(rows, cols, n, n, block=8)
        lhs = block_spmv(mat, 2.0 * x + 3.0 * y, interpret=True)
        rhs = 2.0 * block_spmv(mat, x, interpret=True) + \
            3.0 * block_spmv(mat, y, interpret=True)
        np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs),
                                   rtol=1e-4, atol=1e-4)

    @settings(max_examples=15, deadline=None)
    @given(st.integers(4, 80), st.integers(1, 500),
           st.integers(0, 2 ** 31 - 1))
    def test_property_or_idempotent_monotone(n, m, seed):
        """OR expansion is idempotent in its inputs and monotone in the flag
        set — the properties that make the paper's helping mechanism
        race-free."""
        rows, cols = _random_edges(n, n, m, seed=seed)
        rng = np.random.default_rng(seed + 1)
        f1 = rng.random(n) < 0.2
        f2 = f1 | (rng.random(n) < 0.1)          # superset
        mat = build_block_sparse(rows, cols, n, n, block=8)
        y1 = block_spmv(mat, jnp.asarray(f1, jnp.float32), semiring="or",
                        interpret=True)
        y1b = block_spmv(mat, jnp.asarray(f1, jnp.float32), semiring="or",
                         interpret=True)
        y2 = block_spmv(mat, jnp.asarray(f2, jnp.float32), semiring="or",
                        interpret=True)
        assert bool(jnp.all(y1 == y1b))               # deterministic/idempotent
        assert bool(jnp.all(y2 >= y1))                # monotone
else:                                # pragma: no cover - env-dependent
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_suite_requires_hypothesis():
        pass
