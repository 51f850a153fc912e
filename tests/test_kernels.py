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
