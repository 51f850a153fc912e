"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is found by name in ``BENCHMARK.json``: its configuration file
(``configs/<config>.json``: graph generator and engine settings), its
traffic file (``traffic/<traffic>.json``: the load loop and its
parameters), its limits (``limits/<workload>.json``) and one reader per
metric (``metrics/<metric>.py``).  Adding a cell, a mix or a metric takes
new files and entries, and no edit here.  So does a new deployment: every
key of a configuration file that names an ``EngineConfig`` field reaches
the session (``topology``, ``n_shards``, ``partitioner``, ``exchange``,
``device_budget_bytes``, ``driver``, ...), and a graph generator that
``graphs/generators.py`` does not hold is found in ``graphs/<graph>.py``.
A four-chip cell runs a sharded configuration over its four chips.

Every input is made from ``--seed``.  Set-up builds the graph, opens the
session (its initial ranks come from its own solve on the device), warms
every shape the traffic uses, and the window then runs for ``--seconds``.
Afterwards the answers are checked against the plain reference
(``reference.py``, ``check.py``).  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
end-to-end metrics, or with ``--trace 1`` the per-layer ones), ``device``,
with ``--trace 1`` a ``breakdown``, and last ``checks``, each number
compared beside its limit.  Off a TPU, with fewer chips than the cell
asks for, or with a configuration whose topology does not match the cell's
chips, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse                                                # noqa: E402
import dataclasses                                             # noqa: E402
import gc                                                      # noqa: E402
import importlib.util                                          # noqa: E402
import json                                                    # noqa: E402
import math                                                    # noqa: E402
import os                                                      # noqa: E402
import re                                                      # noqa: E402
import shutil                                                  # noqa: E402
import sys                                                     # noqa: E402
import tempfile                                                # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np                                             # noqa: E402

from bench import check, load, reference                       # noqa: E402
from bench.graphs.generators import edge_keys, make_graph      # noqa: E402
from bench.graphs.stream import (apply_stream, edge_stream,    # noqa: E402
                                 widen_row)
from bench.roofline import peaks                               # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
PLATFORM = "tpu"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# finding a cell's pieces by name
# ---------------------------------------------------------------------------

def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(workload: str, root: str = ROOT) -> dict:
    """Everything one cell needs, found by the names in BENCHMARK.json."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    here = os.path.join(root, "bench")

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return {
        "cell": cell,
        "config": _read_json(os.path.join(root,
                                          configs[cell["config"]]["file"])),
        "traffic": _read_json(os.path.join(here, "traffic",
                                           cell["traffic"] + ".json")),
        "limits": _read_json(os.path.join(here, "limits",
                                          workload + ".json")),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
        "metrics_dir": os.path.join(here, "metrics"),
        "graphs_dir": os.path.join(here, "graphs"),
    }


def load_reader(metrics_dir: str, name: str):
    """``read(run)`` of ``metrics/<name>.py``."""
    path = os.path.join(metrics_dir, name + ".py")
    mod_name = "bench_metric_" + re.sub(r"\W", "_", name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# the device and its compile cache
# ---------------------------------------------------------------------------

def find_chips(chips: int):
    """The devices, or None when JAX finds no TPU or too few of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != PLATFORM:
        log(f"no TPU: JAX found platform {devs[0].platform!r}")
        return None
    if len(devs) < chips:
        log(f"the cell asks for {chips} chips, JAX found {len(devs)}")
        return None
    return devs


def enable_compile_cache(path: str = CACHE_DIR) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    with keys that do not depend on where the checkout lies (as the
    program's ``benchmarks/compile_cache.py`` sets them)."""
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(ROOT + os.sep))


def peak_bytes(devs) -> int:
    stats = [d.memory_stats() or {} for d in devs]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def engine_config(cfg: dict, n: int, backend=None):
    """The session's ``EngineConfig``: every key of the configuration file
    that names one of its fields, with ``tau = tau_rel / n``.  A
    ``single`` topology runs the ``pallas`` engine, and ``backend``, where
    given, takes the place of the file's tile backend; a ``sharded`` one
    leaves the engine to resolve to ``distributed``."""
    from repro.api import EngineConfig
    kw = {f.name: cfg[f.name] for f in dataclasses.fields(EngineConfig)
          if f.name in cfg}
    kw["tau"] = cfg["tau_rel"] / n
    if kw.get("topology", "single") == "single":
        kw["engine"] = "pallas"
        if backend:
            kw["backend"] = backend
    return EngineConfig(**kw)


def topology_error(spec: dict):
    """Why the cell's chips and its configuration's topology disagree, or
    None: a four-chip cell runs a sharded configuration, and a sharded
    configuration states ``n_shards`` equal to the cell's chips."""
    chips, cfg = spec["cell"]["chips"], spec["config"]
    if cfg.get("topology", "single") == "single":
        return None if chips == 1 else (
            f"the cell asks for {chips} chips and its configuration's "
            "topology is 'single'")
    if cfg.get("n_shards") != chips:
        return (f"the configuration is sharded with n_shards="
                f"{cfg.get('n_shards')!r}, the cell asks for {chips} chips")
    return None


def _sizes(part: dict, k: int):
    return [(part["deletions"], part["insertions"])] * k


def _read_ok(values, vertices, n: int, k: int) -> bool:
    v = np.asarray(values)
    return (v.shape == (k,) and bool(np.isfinite(v).all())
            and (vertices is None
                 or bool(((np.asarray(vertices) >= 0)
                          & (np.asarray(vertices) < n)).all())))


def path_check(sess, ecfg, chips: int, *, on_chip: bool) -> None:
    """The session runs the engine its configuration resolves, on as many
    devices as the cell asks for, and on the chip the ``pallas`` engine
    runs compiled Pallas kernels."""
    if sess.engine_name != ecfg.resolved_engine:
        raise RuntimeError(f"the session took engine={sess.engine_name}, "
                           f"its configuration {ecfg.resolved_engine}")
    if on_chip and sess.engine_name == "pallas" and (
            sess.backend != "pallas" or sess.interpret):
        raise RuntimeError(f"the session took backend={sess.backend} "
                           f"interpret={sess.interpret}, not compiled "
                           "Pallas kernels")
    if len(sess.device_footprint) != chips:
        raise RuntimeError(f"the session holds devices "
                           f"{sess.device_footprint}, the cell asks for "
                           f"{chips} chips")


def make_inputs(spec: dict, seed: int, seconds: float) -> dict:
    """Everything the run feeds the program, made from ``seed``: the
    graph (unless the configuration pins it with ``graph_seed``), with the
    row that starts the slot tables at the traffic's ``slot_width``, the
    warm-up batches (one per delta-size bucket the traffic
    reaches), the batches or requests of the window with their due times,
    the vertices of each read, and the probes read after the window.  A
    ``symmetric`` configuration's updates are undirected: each edge is
    both of its arcs."""
    cfg, traffic = spec["config"], spec["traffic"]
    rng = [np.random.default_rng([seed, k]) for k in range(1, 6)]
    # a configuration may pin its graph: then only the traffic follows
    # the seed
    graph_seed = cfg.get("graph_seed")
    n, edges = make_graph(cfg, [seed, 0] if graph_seed is None
                          else graph_seed, spec["graphs_dir"])
    keys0 = edge_keys(n, edges[:, 0], edges[:, 1])
    symmetric = bool(cfg.get("symmetric", False))
    if traffic.get("slot_width"):
        keys0 = widen_row(n, keys0, cfg["block_size"],
                          traffic["slot_width"], rng[4], symmetric=symmetric)
        edges = np.stack([keys0 // n, keys0 % n], 1)
    upd, rd = traffic["update"], traffic.get("read", {})
    out = {"n": n, "edges": edges, "keys0": keys0, "loop": traffic["loop"],
           "query_vertices": rd.get("query_vertices", 16),
           "top_k": rd.get("top_k", 10)}
    if out["loop"] == "closed":
        n_req = traffic["max_batches"]
    elif out["loop"] == "open":
        out["due_updates"] = load.arrivals(upd["rate_per_s"], seconds,
                                           rng[1])
        out["due_reads"] = load.arrivals(rd["rate_per_s"], seconds, rng[2])
        out["read_vertices"] = rng[2].integers(
            0, n, (len(out["due_reads"]), out["query_vertices"]))
        n_req = len(out["due_updates"])
    else:
        raise ValueError(f"traffic loop {out['loop']!r}: closed or open")
    warm = [(w // 2, w - w // 2) for w in traffic.get("warm_edges", ())]
    stream = edge_stream(n, keys0, warm + _sizes(upd, n_req), rng[0],
                         symmetric=symmetric)
    out["warm"], out["batches"] = stream[:len(warm)], stream[len(warm):]
    out["probes"] = rng[3].integers(0, n, (4, out["query_vertices"]))
    return out


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *,
             devs=None, t_start: float = T_START, backend=None) -> dict:
    """Set up, run the window, check, and return the result object."""
    import jax
    from repro.api import PageRankService, PageRankSession, ServingConfig
    from repro.core.graph import HostGraph

    cfg, traffic = spec["config"], spec["traffic"]
    inp = make_inputs(spec, seed, seconds)
    n, edges, keys0 = inp["n"], inp["edges"], inp["keys0"]
    warm_batches, batches = inp["warm"], inp["batches"]
    ecfg = engine_config(cfg, n, backend)
    tau = ecfg.tau
    loop, qv, k_top = inp["loop"], inp["query_vertices"], inp["top_k"]
    log(f"graph {cfg['graph']} seed={seed}: n={n} m={len(keys0)} "
        f"tau={tau:.4e}")
    log(f"[set-up] graph, stream and reads "
        f"{time.perf_counter() - t_start:.3f} s")
    sess = PageRankSession.from_graph(HostGraph(n, edges), config=ecfg)
    del edges
    log(f"[set-up] session and initial solve "
        f"{time.perf_counter() - t_start:.3f} s")
    sess.warmup()
    log(f"[set-up] warmup {time.perf_counter() - t_start:.3f} s")
    path_check(sess, ecfg, spec["cell"]["chips"], on_chip=devs is not None)
    for dels, ins in warm_batches:       # one update per delta bucket
        res = sess.update(dels, ins)
        log(f"[set-up] warm batch of {len(dels) + len(ins)} edges: "
            f"{res.wall_time_s:.3f} s, {res.stats.sweeps} sweeps")
    svc = None
    if loop == "open":
        svc = PageRankService([sess], serving=ServingConfig(
            **traffic.get("serving", {})), warmup=False)
        verts = inp["read_vertices"]

        def make_read(i):
            if i % 2 == 0:
                return lambda: _read_ok(svc.query(0, verts[i]).values,
                                        None, n, qv)
            return lambda: _read_ok(*svc.top_k(0, k_top), n, k_top)
        read_ops = [make_read(i) for i in range(len(verts))]
        for op in read_ops[:2]:          # warm both read shapes
            op()
        svc.start()
    else:
        sess.query(np.zeros(qv, np.int64))
        sess.top_k(k_top)

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        setup_s = time.perf_counter() - t_start
        log(f"set-up {setup_s:.3f} s; window {seconds} s ({loop} loop)")
        if loop == "closed":
            rec = load.run_closed(sess, batches, seconds)
            applied = warm_batches + batches[:rec["applied"]]
        else:
            try:
                rec = load.run_open(svc, batches, inp["due_updates"],
                                    read_ops, inp["due_reads"], seconds)
            finally:
                svc.stop(drain=False)
            applied = warm_batches + [b for b, ok in zip(batches,
                                                         rec["applied"])
                                      if ok]
        if trace:
            jax.profiler.stop_trace()
        tr = None
        if trace:
            from bench import trace as trace_mod
            tr = trace_mod.reduce(trace_mod.find_xplane(trace_dir))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    memory_peak = peak_bytes(devs) if devs is not None else 0

    # answers of the timed path, read back through its own entry points
    reads = []
    for p in inp["probes"]:
        vals = (svc.query(0, p).values if svc is not None
                else sess.query(p))
        reads.append(("query", p, np.asarray(vals)))
    vals, ids = svc.top_k(0, k_top) if svc is not None else \
        sess.top_k(k_top)
    reads.append(("top_k", np.asarray(ids), np.asarray(vals)))
    live = svc.sessions[0] if svc is not None else sess
    ranks = np.asarray(live.ranks)[:n]
    session_keys = edge_keys(n, *live.hg.edges.T)
    report = live.report()
    retraces = (report.retraces_post_warmup,
                report.bucket_retraces_post_warmup)
    # free the program's state before the reference runs
    sess.close()
    if svc is not None:
        del read_ops, make_read
    del sess, svc, live
    gc.collect()

    expected = apply_stream(keys0, n, applied)
    ref = reference.pagerank(n, np.stack([expected // n, expected % n], 1),
                             alpha=ecfg.alpha)
    nums = check.numbers(ranks=ranks, ref=ref, tau=tau,
                         session_keys=session_keys, expected_keys=expected,
                         reads=reads)
    correct, checks = check.judge(nums, spec["limits"])

    run = dict(rec, loop=loop, setup_s=setup_s, seconds=seconds, trace=tr,
               peak=peaks(devs[0].device_kind) if devs else None)
    metrics = {}
    for m in spec["per_layer"] if trace else spec["end_to_end"]:
        v = load_reader(spec["metrics_dir"], m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if loop == "closed":
        attempted = len(rec["batches"])
        failed = sum(not b["converged"] for b in rec["batches"])
    else:
        attempted = len(rec["requests"]) + len(rec["reads"])
        failed = (sum("failed" in r for r in rec["requests"])
                  + sum(not r.get("ok", False) for r in rec["reads"]))
    device = {"platform": devs[0].platform if devs else "cpu",
              "kind": devs[0].device_kind if devs else "cpu",
              "count": len(devs) if devs else 1,
              "memory_peak_bytes": memory_peak}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if tr is not None:
        device.update(busy_s=tr.busy_s, window_s=tr.window_s)
        out["breakdown"] = tr.breakdown()
    log("window batches (s, sweeps): " + " ".join(
        f"{b['end'] - b['start']:.3f}/{b['sweeps']}" for b in rec["batches"]))
    log(f"window: {len(rec['batches'])} batches/dispatches, "
        f"{len(rec['requests'])} requests, {len(rec['reads'])} reads; "
        f"retraces after warmup {retraces[0]} "
        f"(first bucket visits {retraces[1]}); "
        f"peak {memory_peak} B")
    for name, c in checks.items():
        if c["value"] is not None and not math.isfinite(c["value"]):
            c["value"] = None
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    spec = load_spec(args.workload)
    err = topology_error(spec)
    if err is not None:
        log(err)
        return 2
    devs = find_chips(spec["cell"]["chips"])
    if devs is None:
        return 3
    devs = devs[:spec["cell"]["chips"]]
    log(f"[set-up] {devs[0].device_kind} x{len(devs)} found "
        f"{time.perf_counter() - T_START:.3f} s")
    enable_compile_cache()
    out = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                   devs=devs)
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
