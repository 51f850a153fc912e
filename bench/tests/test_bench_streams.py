"""The benchmark's own generators: per-seed determinism, the faithful copy
of the program's graphs, and the edge streams' validity (no edge in two
batches, every deletion an edge and every insertion a non-edge)."""
import numpy as np
import pytest

from bench import load
from bench.graphs import generators as bg
from bench.graphs.stream import apply_stream, edge_stream, widen_row


@pytest.mark.parametrize("make", [lambda s: bg.grid_road(24, seed=s),
                                  lambda s: bg.graph500(9, seed=s)],
                         ids=["grid_road", "graph500"])
def test_graphs_are_deterministic_per_seed(make):
    n, e = make([7, 0])
    n2, e2 = make([7, 0])
    _, e3 = make([8, 0])
    assert n == n2 and np.array_equal(e, e2)
    assert not np.array_equal(e, e3)
    keys = e[:, 0] * n + e[:, 1]
    assert (e[:, 0] != e[:, 1]).all() and (np.diff(keys) > 0).all()


def test_graphs_match_the_program_they_were_copied_from():
    from repro.graphs import generators as pg
    n, e = bg.grid_road(32, seed=3)
    hg = pg.grid_road(32, seed=3)
    assert n == hg.n and np.array_equal(e, hg.edges)


def _stream(seed, sizes=((16, 16),) * 40, symmetric=False):
    n, e = bg.graph500(9, seed=[1, 0])
    keys = bg.edge_keys(n, e[:, 0], e[:, 1])
    return n, keys, edge_stream(n, keys, list(sizes),
                                np.random.default_rng([seed, 1]),
                                symmetric=symmetric)


def test_graph500_is_undirected_and_scrambled():
    n, e = bg.graph500(10, seed=[3, 0])
    keys = e[:, 0] * n + e[:, 1]
    assert np.isin(e[:, 1] * n + e[:, 0], keys).all()
    # unscrambled, the Kronecker hubs would be the lowest ids
    deg = np.bincount(e[:, 0], minlength=n)
    assert deg.max() > 8 * deg.mean()
    assert not np.isin(np.argsort(-deg)[:8], np.arange(8)).all()


def test_stream_is_deterministic_per_seed():
    _, _, a = _stream(5)
    _, _, b = _stream(5)
    _, _, c = _stream(6)
    flat = lambda s: np.concatenate([np.concatenate(x) for x in s])
    assert np.array_equal(flat(a), flat(b))
    assert not np.array_equal(flat(a), flat(c))


@pytest.mark.parametrize("symmetric", [False, True],
                         ids=["directed", "undirected"])
@pytest.mark.parametrize("sizes", [((16, 16),) * 40,
                                   ((4, 4),) * 50 + ((32, 32), (64, 64))])
def test_stream_batches_are_valid_and_disjoint(sizes, symmetric):
    n, keys, batches = _stream(9, sizes, symmetric)
    arcs = 2 if symmetric else 1
    assert [(len(d), len(i)) for d, i in batches] == [
        (arcs * d, arcs * i) for d, i in sizes]
    if symmetric:           # both arcs of every edge, side by side
        for d, i in batches:
            for x in (d, i):
                assert np.array_equal(x[1::2], x[0::2, ::-1])
    seen = np.concatenate([np.concatenate([d[:, 0] * n + d[:, 1],
                                           i[:, 0] * n + i[:, 1]])
                           for d, i in batches])
    assert len(np.unique(seen)) == len(seen)        # no edge twice
    cur = keys
    for d, i in batches:
        dk, ik = d[:, 0] * n + d[:, 1], i[:, 0] * n + i[:, 1]
        assert np.isin(dk, cur).all() and not np.isin(ik, cur).any()
        assert (i[:, 0] != i[:, 1]).all()
        cur = apply_stream(cur, n, [(d, i)])
    assert np.array_equal(cur, apply_stream(keys, n, batches))


def test_arrivals_carry_a_fixed_count():
    for seed in (1, 2, 3):
        t = load.arrivals(20.0, 30.0, np.random.default_rng(seed))
        assert len(t) == 600
        assert (np.diff(t) > 0).all() and 0 < t[0] and t[-1] < 30.0
    a = load.arrivals(4.0, 10.0, np.random.default_rng(1))
    b = load.arrivals(4.0, 10.0, np.random.default_rng(1))
    assert np.array_equal(a, b)


def test_a_pinned_graph_does_not_follow_the_seed():
    from bench import run
    road = run.load_spec("road-1m.serve")
    road["config"].update(side=32, diag_frac=0.05, graph_seed=11)
    road["traffic"].pop("slot_width")
    a, b = run.make_inputs(road, 1, 5.0), run.make_inputs(road, 2, 5.0)
    assert np.array_equal(a["keys0"], b["keys0"])
    assert not np.array_equal(a["batches"][0][1], b["batches"][0][1])
    _, e = bg.grid_road(32, diag_frac=0.05, seed=11)
    assert np.array_equal(a["edges"], e)
    rmat = run.load_spec("rmat-s15.stream")
    rmat["config"]["scale"] = 9
    rmat["traffic"]["max_batches"] = 8
    assert rmat["config"]["graph_seed"] == 11
    a, b = run.make_inputs(rmat, 1, 1.0), run.make_inputs(rmat, 2, 1.0)
    assert np.array_equal(a["keys0"], b["keys0"])
    assert not np.array_equal(a["batches"][0][1], b["batches"][0][1])
    del rmat["config"]["graph_seed"]          # unpinned, the graph follows
    assert not np.array_equal(run.make_inputs(rmat, 1, 1.0)["keys0"],
                              run.make_inputs(rmat, 2, 1.0)["keys0"])


def test_the_road_lattice_is_the_same_for_every_seed():
    from bench import run
    road = run.load_spec("road-1m.serve")
    road["config"]["side"] = 64
    road["traffic"]["slot_width"] = 8
    a, b = run.make_inputs(road, 1, 2.0), run.make_inputs(road, 2, 2.0)
    _, lattice = bg.grid_road(64, diag_frac=0.0, seed=0)
    assert len(lattice) == 4 * 64 * 63
    base = bg.edge_keys(64 * 64, lattice[:, 0], lattice[:, 1])
    for inp in (a, b):
        assert np.isin(base, inp["keys0"]).all()
        extra = np.setdiff1d(inp["keys0"], base)
        assert 0 < len(extra) <= 16             # the widened row's edges
        assert np.isin(extra % 4096 * 4096 + extra // 4096,
                       inp["keys0"]).all()      # undirected


@pytest.mark.parametrize("width", [8, 16])
def test_widen_row_sets_the_slot_width(width):
    n, e = bg.grid_road(64, diag_frac=0.0, seed=0)
    keys = bg.edge_keys(n, e[:, 0], e[:, 1])
    wide = widen_row(n, keys, 64, width, np.random.default_rng(4))
    n_b = n // 64
    per_row = np.bincount(np.unique(np.concatenate([
        (wide % n) // 64 * n_b + (wide // n) // 64,
        np.arange(n_b) * (n_b + 1)])) // n_b, minlength=n_b)
    assert per_row.max() == width // 2 + 1
    assert np.isin(keys, wide).all()
    assert widen_row(n, wide, 64, width, np.random.default_rng(5)).size \
        == wide.size                            # already wide enough


def test_each_kind_of_deletion_falls_alike_for_every_seed():
    """Deletions of a vertex's last edge cost about twice the sweeps: every
    seed's stream has as many of them, in the same batches."""
    n, e = bg.graph500(10, seed=[1, 0])
    keys = bg.edge_keys(n, e[:, 0], e[:, 1])
    deg = np.bincount(e[:, 0], minlength=n)
    counts = []
    for seed in (1, 2, 3):
        batches = edge_stream(n, keys, [(16, 16)] * 60,
                              np.random.default_rng(seed), symmetric=True)
        last = [int((np.minimum(deg[d[::2, 0]], deg[d[::2, 1]]) == 1).sum())
                for d, _ in batches]
        counts.append(last)
        assert all(len(d) == 32 for d, _ in batches)
    assert counts[0] == counts[1] == counts[2] and sum(counts[0]) > 0
