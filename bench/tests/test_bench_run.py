"""A run of each cell at a size a test can hold, on the CPU: the harness's
look for a chip is skipped and the rest of the run is driven as on the
chip.  A sound run comes out correct; the control and each fault the cells
can have come out as not correct.  Off a TPU, ``bench/run.py`` itself
exits non-zero and prints no result."""
import json
import os
import shutil
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from bench import check, control, run
from repro.api import PageRankSession

CELLS = ["rmat-s15.stream", "road-1m.serve", "road-1m.stream"]


def tiny_spec(workload, **sizes):
    spec = run.load_spec(workload)
    cfg = spec["config"]
    closed = spec["traffic"]["loop"] == "closed"
    if "scale" in cfg:
        cfg["scale"] = sizes.get("scale", 9)
    else:
        # a closed loop's 120 batches delete more edges than side 24 has
        cfg["side"] = sizes.get("side", 48 if closed else 24)
    if closed:
        spec["traffic"]["max_batches"] = 120
    return spec


def tiny_run(workload):
    seconds = 0.5 if "stream" in workload else 1.5
    return run.run_cell(tiny_spec(workload), 2**31 + 5, seconds, False,
                        backend="xla")


def _unchanged(orig):
    def update(self, deletions, insertions, **kw):
        time.sleep(0.01)        # takes a step's time, changes nothing
        z = np.zeros((0, 2), np.int64)
        return orig(self, z, z, **kw)
    return update


def _half(orig):
    def update(self, deletions, insertions, **kw):
        d = np.asarray(deletions).reshape(-1, 2)
        i = np.asarray(insertions).reshape(-1, 2)
        return orig(self, d[:len(d) // 2], i[:len(i) // 2], **kw)
    return update


def _altered(orig):
    def query(self, vertices):
        ids = np.asarray(vertices, np.int64).reshape(-1)
        return orig(self, (ids + 1) % self.n)    # off by one vertex
    return query


FAULTS = {"unchanged": ("update", _unchanged),
          "half_batch": ("update", _half),
          "altered_answer": ("query", _altered)}


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    out = tiny_run(workload)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    names = {m["name"] for m in run.load_spec(workload)["end_to_end"]}
    assert set(out["metrics"]) == names
    assert all(v["value"] > 0 for v in out["metrics"].values())
    json.dumps(out, allow_nan=False)


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_fault_comes_out_incorrect(workload, fault, monkeypatch):
    method, plant = FAULTS[fault]
    monkeypatch.setattr(PageRankSession, method,
                        plant(getattr(PageRankSession, method)))
    out = tiny_run(workload)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_comes_out_incorrect(workload):
    """The reference in bfloat16, in the program's place, fails the cell's
    limits (at a size the reference solves in a second)."""
    spec = tiny_spec(workload, scale=12, side=128)
    for seed in (1, 2, 3):
        nums = control.control_numbers(spec, seed, batches=8)
        correct, checks = check.judge(nums, spec["limits"])
        assert not correct, checks
        assert nums["graph_diff"] == 0


def _result_lines(stdout):
    return [ln for ln in stdout.splitlines() if ln.startswith("{")]


def test_run_off_a_tpu_exits_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(run.ROOT, "bench", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0 and not _result_lines(p.stdout)
    assert "no TPU" in p.stderr


def test_run_without_the_program_exits_without_result(tmp_path):
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(run.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[1], "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and not _result_lines(p.stdout)


def test_tiered_cell_is_correct(monkeypatch):
    """A configuration's ``device_budget_bytes`` reaches the session: the
    road cell with its tile pool tiered under a budget of about half the
    pool (2 MiB of 64 x 64 f32 tiles at side 48) comes out correct."""
    spec = tiny_spec("road-1m.stream")
    spec["config"]["device_budget_bytes"] = 2 * 1024 * 1024
    tiering = []
    orig = run.path_check

    def record(sess, ecfg, chips, **kw):
        tiering.append(sess.report().tiering)
        return orig(sess, ecfg, chips, **kw)

    monkeypatch.setattr(run, "path_check", record)
    out = run.run_cell(spec, 2**31 + 7, 0.5, False, backend="xla")
    assert tiering[0]["budget_bytes"] == 2 * 1024 * 1024
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


_SHARDED = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{root!r}, {src!r}]
    import jax
    assert len(jax.devices()) == 4
    from bench import run
    spec = run.load_spec("road-1m.stream")
    spec["cell"] = dict(spec["cell"], chips=4)
    cfg = spec["config"]
    cfg.update(side=48, topology="sharded", n_shards=4,
               partitioner="contiguous", exchange="full")
    spec["traffic"]["max_batches"] = 120
    assert run.topology_error(spec) is None
    footprints = []
    orig = run.path_check
    def record(sess, ecfg, chips, **kw):
        footprints.append((sess.engine_name, len(sess.device_footprint)))
        return orig(sess, ecfg, chips, **kw)
    run.path_check = record
    out = run.run_cell(spec, 2**31 + 9, 0.5, False)
    print(json.dumps({{"footprints": footprints, "correct": out["correct"],
                      "attempted": out["attempted"],
                      "failed": out["failed"], "checks": out["checks"]}}))
""")


@pytest.mark.multidevice
def test_sharded_cell_is_correct_on_four_devices():
    """A sharded configuration over four (forced host) devices runs the
    ``distributed`` engine through the whole run and comes out correct."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = _SHARDED.format(root=run.ROOT,
                             src=os.path.join(run.ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(_result_lines(p.stdout)[-1])
    assert out["footprints"] == [["distributed", 4]]
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def _four_chip_tree(tmp_path, cfg_update):
    """A copy of the benchmark with one further cell on four chips, whose
    configuration is road-1m's with ``cfg_update``."""
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(run.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "road-x", "source": "test",
                         "file": "bench/configs/road-x.json",
                         "reduced": ["side"], "why": "test"})
    b["workloads"].append({"name": "road-x.stream", "config": "road-x",
                           "traffic": "stream-w16", "chips": 4,
                           "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cfg = json.loads((tmp_path / "bench/configs/road-1m.json").read_text())
    cfg.update(cfg_update)
    (tmp_path / "bench/configs/road-x.json").write_text(json.dumps(cfg))
    shutil.copy(tmp_path / "bench/limits/road-1m.stream.json",
                tmp_path / "bench/limits/road-x.stream.json")


@pytest.mark.parametrize("cfg_update, why", [
    ({}, "topology is 'single'"),
    ({"topology": "sharded"}, "n_shards=None"),
    ({"topology": "sharded", "n_shards": 2}, "n_shards=2"),
], ids=["four_chips_single", "sharded_no_n_shards", "sharded_n_shards_2"])
def test_topology_and_chips_that_disagree_exit_without_result(
        tmp_path, cfg_update, why):
    """Before set-up, a four-chip cell of a single-topology configuration,
    or a sharded configuration whose ``n_shards`` is not the cell's chips,
    exits non-zero with no result line."""
    _four_chip_tree(tmp_path, cfg_update)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "road-x.stream",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and not _result_lines(p.stdout)
    assert why in p.stderr and "no TPU" not in p.stderr
