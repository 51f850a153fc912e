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
import time

import numpy as np
import pytest

from bench import check, control, run
from repro.api import PageRankSession

CELLS = ["rmat-s15.stream", "road-1m.serve"]


def tiny_spec(workload, **sizes):
    spec = run.load_spec(workload)
    cfg = spec["config"]
    if "scale" in cfg:
        cfg["scale"] = sizes.get("scale", 9)
    else:
        cfg["side"] = sizes.get("side", 24)
    if spec["traffic"]["loop"] == "closed":
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
