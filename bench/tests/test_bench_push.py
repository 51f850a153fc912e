"""The push deployment's cell, ``rmat-s15-push.stream``, at a size a test
can hold, on the CPU: its configuration is ``rmat-s15``'s with the push
driver, a sound run comes out correct, the control and each planted fault
do not, and the reader of the driver's host seeding reads its spans."""
import json
import os

import pytest

from bench import check, control, run, trace
from bench.tests.test_bench_run import FAULTS, tiny_run, tiny_spec
from repro.api import PageRankSession
from repro.core.graph import HostGraph

CELL = "rmat-s15-push.stream"


def test_configuration_is_the_pull_cells_with_the_push_driver():
    def cfg(name):
        with open(os.path.join(run.ROOT, "bench", "configs",
                               name + ".json")) as f:
            return json.load(f)

    pull, push = cfg("rmat-s15"), cfg("rmat-s15-push")
    assert (pull.pop("driver"), push.pop("driver")) == ("pull", "push")
    assert set(push["assumed"]) - set(pull["assumed"]) == {"driver"}
    for c in (pull, push):
        for k in ("name", "source", "assumed"):
            c.pop(k)
    assert push == pull
    spec = run.load_spec(CELL)
    assert spec["traffic"] == run.load_spec("rmat-s15.stream")["traffic"]
    assert spec["cell"]["chips"] == 1


def test_configuration_builds_a_push_session_that_passes_the_path_check():
    spec = tiny_spec(CELL)
    inp = run.make_inputs(spec, 2**31 + 5, 0.5)
    ecfg = run.engine_config(spec["config"], inp["n"], "xla")
    assert (ecfg.driver, ecfg.engine) == ("push", "pallas")
    sess = PageRankSession.from_graph(HostGraph(inp["n"], inp["edges"]),
                                      config=ecfg)
    run.path_check(sess, ecfg, spec["cell"]["chips"], on_chip=False)
    assert sess.engine_name == "pallas" and sess._push
    sess.close()


def test_sound_run_is_correct():
    out = tiny_run(CELL)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"update_s", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    json.dumps(out, allow_nan=False)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_comes_out_incorrect(fault, monkeypatch):
    method, plant = FAULTS[fault]
    monkeypatch.setattr(PageRankSession, method,
                        plant(getattr(PageRankSession, method)))
    out = tiny_run(CELL)
    assert not out["correct"], out["checks"]


def test_control_comes_out_incorrect():
    """The reference in bfloat16, in the program's place, fails the cell's
    limits (at a size the reference solves in a second)."""
    spec = tiny_spec(CELL, scale=12)
    for seed in (1, 2, 3):
        nums = control.control_numbers(spec, seed, batches=8)
        correct, checks = check.judge(nums, spec["limits"])
        assert not correct, checks
        assert nums["graph_diff"] == 0


def _read(tr, loop="closed"):
    spec = run.load_spec(CELL)
    return run.load_reader(spec["metrics_dir"], "push_seed_s.stream")(
        {"trace": tr, "loop": loop})


def _trace(host_events):
    return trace.Trace(window=(0.0, 10.0), busy={}, op_s={}, spans={},
                       host_events=[("bench.window", 0.0, 10.0)]
                       + list(host_events))


# two updates in the window whose seeds hold push seedings of 0.1 s and
# 0.2 s; the third update starts before the window
PUSH_UPDATES = [("session.update", 1.0, 2.0), ("session.seed", 1.2, 1.4),
                ("session.push_seed", 1.25, 1.35),
                ("session.update", 4.0, 6.0), ("session.seed", 4.1, 4.5),
                ("session.push_seed", 4.2, 4.4),
                ("session.update", -1.0, 0.5),
                ("session.push_seed", -0.5, 0.4)]


def test_push_seed_s_is_the_mean_push_seeding_per_update():
    assert _read(_trace(PUSH_UPDATES)) == pytest.approx((0.1 + 0.2) / 2)


@pytest.mark.parametrize("case", ["no trace", "open loop", "no span"])
def test_push_seed_s_reads_nothing_without_its_spans(case):
    if case == "no trace":
        assert _read(None) is None
    elif case == "open loop":
        assert _read(_trace(PUSH_UPDATES), "open") is None
    else:       # a pull session, or a program that opens no such span
        pull = [e for e in PUSH_UPDATES if e[0] != "session.push_seed"]
        assert _read(_trace(pull)) is None
