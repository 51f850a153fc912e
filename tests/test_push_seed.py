"""The push driver's host seeding of a batch: the read of the ranks at the
batch's source vertices goes through one bucketed device gather, so a
stream whose batches touch varying numbers of sources compiles that read
once, not once per count."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.api import EngineConfig, PageRankSession
from repro.core import push_engine as pshe
from repro.core.delta import random_batch
from repro.graphs.generators import rmat


@pytest.mark.parametrize("k", [0, 1, 37, 64, 65, 300])
def test_gather_host_reads_the_indexed_values(k):
    x = jnp.asarray(np.random.default_rng(k).random(512))
    idx = np.random.default_rng(k + 1).integers(0, 512, k)
    got = pshe.gather_host(x, idx)
    assert got.shape == (k,)
    np.testing.assert_array_equal(got, np.asarray(x)[idx])


def test_gather_host_compiles_once_for_every_count_in_a_bucket():
    x = jnp.zeros((1000,), jnp.float32)
    pshe.gather_host(x, np.arange(1))
    before = pshe._gather._cache_size()
    for k in range(1, 65):
        pshe.gather_host(x, np.arange(k))
    assert pshe._gather._cache_size() == before


def test_push_update_reads_the_sources_through_the_gather(monkeypatch):
    hg = rmat(8, avg_degree=5, seed=11)
    sess = PageRankSession.from_graph(hg, config=EngineConfig(
        engine="pallas", block_size=64, driver="push"))
    sess.warmup()
    counts, orig = [], pshe.gather_host

    def spy(x, idx):
        counts.append(len(idx))
        return orig(x, idx)

    monkeypatch.setattr(pshe, "gather_host", spy)
    cur = hg
    for i, frac in enumerate([5e-3, 1e-2, 2e-2]):
        dels, ins = random_batch(cur, frac, seed=i)
        cur = cur.apply_batch(dels, ins)
        sess.update(dels, ins)
    assert len(counts) == 3 and len(set(counts)) == 3, counts
    sess.close()
