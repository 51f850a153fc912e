"""Compiles of the main path for a described TPU v5e, at real size.

Nothing here runs: the TPU compiler, which is installed with jax, compiles
for a ``v5e:2x2`` topology that is described, not attached.  That catches
what interpret mode cannot — scalar-memory (SMEM) overflow of the
prefetched slot tables, index maps that trace to int64 under x64, block
shapes the chip refuses — at the n = 1,048,576 layout of
``grid_road(1024)``: 16,384 row-blocks of B = 64, 16 tile slots per row,
float32 ranks, and the kernel also at the ``rmat-s15`` layout of 512
row-blocks of 512 slots.  The suite enables x64, so these compiles also
guard the kernels against it.  One more case lowers a kernel from two
checkouts and checks that the scripts' cache settings give both the same
cache key.

The topology is described inside a module-scoped fixture (never at
import): only one process may load the TPU library at a time, so only the
worker that runs this file loads it.  The persistent compilation cache is
off around these compiles; an entry written for a described chip cannot be
read back without one.
"""
import importlib.util
import os
import shutil

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from benchmarks import compile_cache
from repro.core import pallas_engine as pe
from repro.core import push_engine as pshe
from repro.kernels.block_spmv import block_spmv as bk
from repro.kernels.block_spmv.ops import BlockSparse, tile_shape

N_RB, MAX_TILES, B = 16_384, 16, 64
N_PAD = N_RB * B                       # 1,048,576 vertices
TILE_CAP = 131_072                     # ~2.15 GB of f32 64x64 tiles
MAX_ITERATIONS = 500


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)
    cc.reset_cache()


def _spec(one_chip):
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)


def _mat(S):
    return BlockSparse(n_rows=N_PAD, n_cols=N_PAD, block=B,
                       max_tiles=MAX_TILES,
                       tiles=S((TILE_CAP,) + tile_shape(B), jnp.float32),
                       tile_cols=S((N_RB, MAX_TILES), jnp.int32),
                       tile_idx=S((N_RB * MAX_TILES,), jnp.int32))


@pytest.mark.parametrize("semiring", ["sum", "or"])
@pytest.mark.parametrize("kernel", ["full", "active"])
def test_kernel_compiles_at_1m(one_chip, kernel, semiring):
    S = _spec(one_chip)
    m = _mat(S)
    args = (m.tile_idx, m.tile_cols, m.tiles, S((N_PAD,), jnp.float32))
    kw = dict(block=B, max_tiles=MAX_TILES, semiring=semiring)
    if kernel == "full":
        lowered = bk.block_spmv_pallas.lower(*args, **kw)
    else:       # the top ladder bucket: every row-block active
        lowered = bk.block_spmv_active_pallas.lower(
            S((N_RB,), jnp.int32), *args, **kw)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("semiring", ["sum", "or"])
def test_active_kernel_compiles_at_rmat_s15(one_chip, semiring):
    """The ``rmat-s15`` layout: 512 row-blocks of 512 slots each (32,768
    vertices, a 262,144-tile pool), every row-block active — a launch of
    wide rows whose slot tables split into several launches."""
    S = _spec(one_chip)
    n_rb, mt, cap = 512, 512, 262_144
    compiled = bk.block_spmv_active_pallas.lower(
        S((n_rb,), jnp.int32), S((n_rb * mt,), jnp.int32),
        S((n_rb, mt), jnp.int32), S((cap,) + tile_shape(B), jnp.float32),
        S((n_rb * B,), jnp.float32), block=B, max_tiles=mt,
        semiring=semiring).compile()
    assert bk.launch_rows(mt) < n_rb
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def pull_driver_hlo(one_chip):
    """Text of the fused pull driver compiled at the 1M layout."""
    S = _spec(one_chip)
    f32, b = jnp.float32, jnp.bool_
    return pe._driver.lower(
        _mat(S), S((N_PAD,), f32), S((N_PAD,), b), S((N_PAD,), b),
        S((N_PAD,), jnp.int32), S((N_RB,), jnp.int32),
        S((N_RB,), jnp.int32), S((N_RB, N_RB), b), S((N_RB,), b),
        S((), f32), S((), f32), S((), f32),
        S((MAX_ITERATIONS, 1), b), S((MAX_ITERATIONS, 1), b),
        S((MAX_ITERATIONS, 1), f32), S((MAX_ITERATIONS,), b),
        n=N_PAD, block_size=B, mode="lf", expand=True,
        active_policy="affected", max_iterations=MAX_ITERATIONS,
        interpret=False, backend="pallas").compile().as_text()


def test_pull_driver_compiles_at_1m(pull_driver_hlo):
    assert "tpu_custom_call" in pull_driver_hlo


def test_pull_driver_scopes_leave_instruction_names(pull_driver_hlo):
    """The pull driver's named scopes reach the kernel launches' op
    metadata only: the launches stay ``tpu_custom_call`` custom calls, and no
    custom-call instruction takes a scope's name (a device trace names
    ops by instruction, and the benchmark's readers match those names)."""
    calls = [ln.split(" = ", 1) for ln in pull_driver_hlo.splitlines()
             if " custom-call(" in ln]
    kernels = [rhs for _, rhs in calls
               if 'custom_call_target="tpu_custom_call"' in rhs]
    assert kernels
    for lhs, _ in calls:
        name = lhs.strip().lstrip("%")
        assert not name.startswith(("spmv.", "df.", "delta.")), name
    scoped = {sem: any(f"/spmv.{sem}.k" in rhs for rhs in kernels)
              for sem in ("sum", "or")}
    assert scoped == {"sum": True, "or": True}
    assert any("/df.sweep/" in rhs for rhs in kernels)
    assert any("/df.expand/" in rhs for rhs in kernels)


def test_push_driver_compiles_at_1m(one_chip):
    S = _spec(one_chip)
    f32, b = jnp.float32, jnp.bool_
    compiled = pshe._push_driver.lower(
        _mat(S), S((N_PAD,), f32), S((N_PAD,), f32), S((N_PAD,), b),
        S((N_PAD,), jnp.int32), S((N_RB,), jnp.int32),
        S((N_RB, N_RB), b), S((N_RB,), b), S((), f32), S((), f32),
        n=N_PAD, block_size=B, max_iterations=MAX_ITERATIONS,
        interpret=False, backend="pallas").compile()
    assert "tpu_custom_call" in compiled.as_text()


KERNEL_SRC = os.path.join("src", "repro", "kernels", "block_spmv",
                          "block_spmv.py")


def _lowered_from(root, one_chip):
    """Text of the active kernel lowered from the copy of its source file
    under the checkout ``root`` (debug info off, as in the cache key): the
    Mosaic payload inside it keeps the kernel's source locations."""
    spec = importlib.util.spec_from_file_location("block_spmv_copy",
                                                  root / KERNEL_SRC)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    S = _spec(one_chip)
    n_rb, mt = 64, 4
    return mod.block_spmv_active_pallas.lower(
        S((n_rb,), jnp.int32), S((n_rb * mt,), jnp.int32),
        S((n_rb, mt), jnp.int32), S((256,) + tile_shape(B), jnp.float32),
        S((n_rb * B,), jnp.float32), block=B, max_tiles=mt).as_text()


def _lowered_via_caller(root, one_chip):
    return _lowered_from(root, one_chip)


@pytest.mark.parametrize("scripts_settings", [True, False])
def test_kernel_cache_key_follows_checkout(one_chip, tmp_path, monkeypatch,
                                           scripts_settings):
    """With ``enable_compile_cache``'s settings, two checkouts of the same
    kernel, reached through different callers, lower to the same program
    text, so their cache entries are shared; without them the absolute
    source path and the call stack tell them apart."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    names = ("jax_include_full_tracebacks_in_locations",
             "jax_hlo_source_file_canonicalization_regex",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in names}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    texts = []
    try:
        for co in ("a", "b"):
            root = tmp_path / co / "checkout"
            os.makedirs(os.path.dirname(root / KERNEL_SRC))
            shutil.copy(os.path.join(repo, KERNEL_SRC), root / KERNEL_SRC)
            if scripts_settings:
                monkeypatch.setattr(compile_cache, "CHECKOUT", str(root))
                compile_cache.enable_compile_cache()
            lower = _lowered_from if co == "a" else _lowered_via_caller
            texts.append(lower(root, one_chip))
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
    assert "tpu_custom_call" in texts[0]
    assert (texts[0] == texts[1]) is scripts_settings
