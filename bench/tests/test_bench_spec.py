"""BENCHMARK.json against the benchmark's contract, every piece found by
name, and a further configuration, traffic mix and metric found from new
files alone."""
import json
import os
import re
import shutil

import numpy as np
import pytest

from bench import run
from repro.api import EngineConfig, PageRankSession
from repro.core.graph import HostGraph

ROOT = run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _bench(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_benchmark_json_keeps_the_contract():
    b = _bench()
    assert set(b) == KEYS["top"]
    assert b["paths"] == ["bench"] and b["command"][1] == "bench/run.py"
    rs = b["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert 1200 + 24 * 180 + (2 + 14 * 24) * (rs + 60) <= 43200
    names = set()
    for part in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in b[part]:
            extra = {"workloads"} if part in ("end_to_end",
                                              "per_layer") else set()
            assert KEYS[part] <= set(e) <= KEYS[part] | extra, e
            assert NAME.match(e["name"]) and e["name"] not in names
            names.add(e["name"])
    cfgs = {c["name"]: c for c in b["configs"]}
    for c in cfgs.values():
        assert c["file"].startswith("bench/") and _line(c["source"])
        with open(os.path.join(ROOT, c["file"])) as f:
            assert sorted(json.load(f)["reduced"]) == sorted(c["reduced"])
    cells = {w["name"]: w for w in b["workloads"]}
    assert {w["config"] for w in cells.values()} == set(cfgs)
    for w in cells.values():
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert NAME.match(w["traffic"])
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"]) and m["moves"] in e2e
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", cells)
    for w in cells:
        spec = run.load_spec(w)
        got = {m["name"] for m in spec["end_to_end"]}
        assert "setup_s" in got and len(got) >= 2
        assert spec["per_layer"]


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      _bench()["workloads"]])
def test_every_piece_loads_by_name(workload):
    spec = run.load_spec(workload)
    assert spec["cell"]["name"] == workload
    assert spec["traffic"]["loop"] in ("closed", "open")
    assert {"rank_err_tau", "graph_diff", "read_err_tau"} <= set(
        spec["limits"])
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(run.load_reader(spec["metrics_dir"], m["name"]))


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A configuration, a traffic mix and a metric added as files, with
    entries in BENCHMARK.json, are found and used with no edit."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    b = _bench()
    b["configs"].append({"name": "road-tiny", "source": "test",
                         "file": "bench/configs/road-tiny.json",
                         "reduced": ["side"], "why": "test"})
    b["workloads"].append({"name": "road-tiny.burst", "config": "road-tiny",
                           "traffic": "burst", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "requests_per_dispatch", "unit": "1",
                           "better": "higher", "source": "program_span",
                           "layer": "service (api/service)",
                           "moves": "visible_p95_ms",
                           "workloads": ["road-tiny.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cfg = json.loads((tmp_path / "bench/configs/road-1m.json").read_text())
    cfg.update(name="road-tiny", side=16)
    (tmp_path / "bench/configs/road-tiny.json").write_text(json.dumps(cfg))
    traffic = {"loop": "open",
               "update": {"deletions": 2, "insertions": 6,
                          "rate_per_s": 50.0},
               "read": {"rate_per_s": 5.0, "query_vertices": 4,
                        "top_k": 3},
               "warm_edges": [64]}
    (tmp_path / "bench/traffic/burst.json").write_text(json.dumps(traffic))
    (tmp_path / "bench/limits/road-tiny.burst.json").write_text(
        json.dumps({"rank_err_tau": 1.0, "graph_diff": 0,
                    "read_err_tau": 1.0}))
    (tmp_path / "bench/metrics/requests_per_dispatch.py").write_text(
        "def read(run):\n"
        "    b = run['batches']\n"
        "    return sum(x['requests'] for x in b) / len(b) if b else None\n")

    spec = run.load_spec("road-tiny.burst", root=str(tmp_path))
    assert spec["config"]["side"] == 16 and spec["traffic"] == traffic
    assert spec["limits"]["rank_err_tau"] == 1.0
    assert [m["name"] for m in spec["per_layer"]] == ["requests_per_dispatch"]
    read = run.load_reader(spec["metrics_dir"], "requests_per_dispatch")
    assert read({"batches": [{"requests": 3}, {"requests": 5}]}) == 4.0
    inp = run.make_inputs(spec, 3, 2.0)
    assert inp["n"] == 256 and len(inp["due_updates"]) == 100
    # road-1m's graph is undirected: each edge of a request is two arcs
    assert [(len(d), len(i)) for d, i in inp["batches"][:2]] == [(4, 12)] * 2
    assert inp["read_vertices"].shape == (10, 4)
    assert len(inp["warm"]) == 1 and np.array_equal(
        inp["batches"][0][0], run.make_inputs(spec, 3, 2.0)["batches"][0][0])
    # the cells already there still load from the same tree
    assert run.load_spec("road-1m.serve", root=str(tmp_path))["per_layer"]

    # a graph generator found by its own file, and the engine keys of a
    # configuration reaching the session, with no edit either
    (tmp_path / "bench/graphs/ring_lattice.py").write_text(
        "import numpy as np\n"
        "def ring_lattice(ring, *, seed):\n"
        "    v = np.arange(ring, dtype=np.int64)\n"
        "    s = np.concatenate([v, (v + 1) % ring])\n"
        "    d = np.concatenate([(v + 1) % ring, v])\n"
        "    k = np.unique(s * ring + d)\n"
        "    return ring, np.stack([k // ring, k % ring], 1)\n")
    ring = {"graph": "ring_lattice", "ring": 200, "symmetric": True,
            "alpha": 0.85, "tau_rel": 1e-4, "dtype": "float32",
            "block_size": 64, "reduced": []}
    variants = {
        "ring-sharded": dict(ring, topology="sharded", n_shards=1,
                             partitioner="hash", exchange="bf16"),
        "ring-tiered": dict(ring, backend="xla",
                            device_budget_bytes=1 << 20),
    }
    for name, cfg in variants.items():
        b["configs"].append({"name": name, "source": "test",
                             "file": f"bench/configs/{name}.json",
                             "reduced": [], "why": "test"})
        b["workloads"].append({"name": name + ".trickle", "config": name,
                               "traffic": "trickle", "chips": 1,
                               "why": "test"})
        (tmp_path / f"bench/configs/{name}.json").write_text(
            json.dumps(cfg))
        (tmp_path / f"bench/limits/{name}.trickle.json").write_text(
            json.dumps({"rank_err_tau": 1.0, "graph_diff": 0,
                        "read_err_tau": 1.0}))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    (tmp_path / "bench/traffic/trickle.json").write_text(json.dumps(
        {"loop": "closed", "update": {"deletions": 2, "insertions": 2},
         "max_batches": 4}))
    for name, cfg in variants.items():
        spec = run.load_spec(name + ".trickle", root=str(tmp_path))
        assert run.topology_error(spec) is None
        inp = run.make_inputs(spec, 3, 2.0)
        assert inp["n"] == 200 and len(inp["keys0"]) == 400
        ecfg = run.engine_config(spec["config"], inp["n"])
        sess = PageRankSession.from_graph(
            HostGraph(inp["n"], inp["edges"]), config=ecfg)
        try:
            for key in set(cfg) & set(EngineConfig.valid_keys()):
                assert getattr(sess.config, key) == cfg[key], key
            assert sess.engine_name == ecfg.resolved_engine == (
                "distributed" if "topology" in cfg else "pallas")
        finally:
            sess.close()


@pytest.mark.parametrize("config", ["rmat-s15", "road-1m"])
@pytest.mark.parametrize("backend", [None, "xla"])
def test_accepted_configuration_builds_the_same_engine_config(config,
                                                              backend):
    """Each accepted configuration's ``EngineConfig`` equals, field by
    field, the one the harness built from its eight keys before a
    configuration could carry the other engine axes."""
    with open(os.path.join(ROOT, "bench", "configs", config + ".json")) as f:
        cfg = json.load(f)
    n = 1 << 15
    before = EngineConfig(
        engine="pallas", backend=backend or cfg["backend"],
        driver=cfg["driver"], mode=cfg["mode"], dtype=cfg["dtype"],
        alpha=cfg["alpha"], tau=cfg["tau_rel"] / n,
        block_size=cfg["block_size"])
    assert run.engine_config(cfg, n, backend) == before
