"""The control of the comparison that decides ``correct``.

    python bench/control.py --workload <name> --seeds 1 2 3 --batches K

For each seed it makes the cell's inputs as a run does, applies the
warm-up batches and the first ``K`` batches or requests of the window to
the graph, and puts the reference computed in bfloat16 (``reference.py``)
in the program's place: its ranks and its answers to the same reads go
through the same comparison, against the float64 reference.  The control
has to come out as not correct; the smallest reading it gives bounds each
limit from above.  The benchmark's own runs never run it.  It needs no
chip: the reference runs on the host.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np                                              # noqa: E402

from bench import check, reference                             # noqa: E402
from bench.graphs.stream import apply_stream                   # noqa: E402
from bench.run import load_spec, make_inputs                   # noqa: E402


def control_numbers(spec: dict, seed: int, batches: int,
                    seconds: float = 51.0) -> dict:
    """The comparison's numbers with the bfloat16 reference as the
    program, on the graph after ``batches`` batches of the window."""
    inp = make_inputs(spec, seed, seconds)
    n, cfg = inp["n"], spec["config"]
    keys = apply_stream(inp["keys0"], n,
                        inp["warm"] + inp["batches"][:batches])
    edges = np.stack([keys // n, keys % n], 1)
    ref = reference.pagerank(n, edges, alpha=cfg["alpha"])
    ctl = reference.pagerank(n, edges, alpha=cfg["alpha"],
                             precision="bfloat16")
    reads = [("query", p, ctl[p]) for p in inp["probes"]]
    top = np.argsort(-ctl, kind="stable")[:inp["top_k"]]
    reads.append(("top_k", top, ctl[top]))
    return check.numbers(ranks=ctl, ref=ref, tau=cfg["tau_rel"] / n,
                         session_keys=keys, expected_keys=keys, reads=reads)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--batches", type=int, required=True,
                    help="batches or requests of the window applied")
    args = ap.parse_args(argv)
    spec = load_spec(args.workload)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = json.load(f)["run_seconds"]
    for seed in args.seeds:
        nums = control_numbers(spec, seed, args.batches, seconds)
        correct, checks = check.judge(nums, spec["limits"])
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "batches": args.batches, "correct": correct,
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
