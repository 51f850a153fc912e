"""The two load loops a traffic file can name, driving the program's own
entry points.

``closed``: one client applies update batches back to back through
``PageRankSession.update`` until the window has passed; the batch in flight
at the close is allowed to finish.

``open``: requests arrive on a schedule drawn from the seed, whatever the
system does.  Update requests go to ``PageRankService.submit`` and reads to
``PageRankService.query`` / ``top_k`` from a second thread, with the
service's own worker thread dispatching (``start()``).  Every request is
timed from the moment it was due.

Both loops record only host clocks and the program's own records and
counters; the spans they open (``bench.*``) let the trace reduction put
each call on the device's clock.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, List

import numpy as np
from jax.profiler import TraceAnnotation

#: a due read or request is issued at most this long after the window
#: closes; what is still waiting after it counts as failed
LATE_LIMIT_S = 60.0


def arrivals(rate: float, seconds: float, rng: np.random.Generator
             ) -> np.ndarray:
    """Due times in [0, seconds) of a Poisson process at ``rate``,
    conditioned on its count: exactly ``round(rate * seconds)`` arrivals,
    so that every seed carries the same amount of work."""
    k = int(round(rate * seconds))
    gaps = rng.exponential(size=k + 1)
    return seconds * np.cumsum(gaps)[:k] / gaps.sum()


def sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


def run_closed(sess, batches: List, seconds: float) -> dict:
    """Apply ``batches`` in order until ``seconds`` have passed."""
    recs = []
    with TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        for dels, ins in batches:
            if time.perf_counter() - t0 >= seconds:
                break
            start = time.perf_counter()
            with TraceAnnotation("bench.update"):
                res = sess.update(dels, ins)
            end = time.perf_counter()
            recs.append({"start": start - t0, "end": end - t0,
                         "sweeps": res.stats.sweeps,
                         "edges": res.stats.edges_processed,
                         "converged": bool(res.converged)})
        t1 = time.perf_counter()
    if recs and time.perf_counter() - t0 < seconds:
        raise RuntimeError(f"the stream ran out after {len(recs)} batches, "
                           f"before the {seconds} s window closed")
    return {"batches": recs, "applied": len(recs), "window_s": t1 - t0,
            "requests": [], "reads": []}


def run_open(svc, requests: List, due_updates: np.ndarray,
             read_ops: List[Callable[[], bool]], due_reads: np.ndarray,
             seconds: float) -> dict:
    """Submit ``requests`` at ``due_updates`` and call ``read_ops[i]``
    at ``due_reads[i]`` (seconds from the window's start; each returns
    whether its answer was well formed), then wait for every accepted
    request to be dispatched and visible."""
    from repro.api import AdmissionRejected

    # when each dispatch became visible: the service refreshes its read
    # snapshot right after a dispatch retires its requests.  This wraps the
    # one private method the benchmark touches; it fails loudly if it goes.
    visible: List[float] = []
    refresh = svc._refresh_snapshot

    def refresh_and_record(stream):
        refresh(stream)
        visible.append(time.perf_counter())

    svc._refresh_snapshot = refresh_and_record
    reqs = [{"due": float(d)} for d in due_updates]
    reads = [{"due": float(d)} for d in due_reads]
    uids = {}
    errors: List[BaseException] = []

    def updater(t0):
        try:
            for rec, (dels, ins) in zip(reqs, requests):
                sleep_until(t0 + rec["due"])
                if time.perf_counter() - t0 > seconds + LATE_LIMIT_S:
                    rec["failed"] = "late"
                    continue
                try:
                    with TraceAnnotation("bench.submit"):
                        uids[svc.submit(0, dels, ins)] = rec
                except AdmissionRejected as e:
                    rec["failed"] = e.reason["code"]
        except BaseException as e:          # surfaced after the join
            errors.append(e)

    def reader(t0):
        try:
            for i, rec in enumerate(reads):
                sleep_until(t0 + rec["due"])
                if time.perf_counter() - t0 > seconds + LATE_LIMIT_S:
                    rec["failed"] = "late"
                    continue
                with TraceAnnotation("bench.read"):
                    rec["ok"] = bool(read_ops[i]())
                rec["done"] = time.perf_counter() - t0
        except BaseException as e:
            errors.append(e)

    with TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        threads = [threading.Thread(target=f, args=(t0,), name=f.__name__)
                   for f in (updater, reader)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=seconds + 2 * LATE_LIMIT_S)
        if any(t.is_alive() for t in threads):
            raise RuntimeError("the load threads did not finish")
        if errors:
            raise errors[0]
        svc.run_until_drained()
        t1 = time.perf_counter()
    del svc._refresh_snapshot

    done = {r.uid: r for r in svc.finished}
    dispatches = {}
    vis = np.asarray(sorted(visible))
    for uid, rec in uids.items():
        r = done.get(uid)
        if r is None or r.error is not None:
            rec["failed"] = "not done" if r is None else r.error
            continue
        rec.update(submitted=r.submitted_s - t0, started=r.started_s - t0,
                   done=r.done_s - t0)
        i = int(np.searchsorted(vis, r.done_s))
        rec["visible"] = (vis[i] if i < len(vis) else t1) - t0
        d = dispatches.setdefault(r.started_s, {
            "start": r.started_s - t0, "end": r.done_s - t0,
            "sweeps": r.result.stats.sweeps,
            "edges": r.result.stats.edges_processed,
            "converged": bool(r.result.converged), "requests": 0})
        d["requests"] += 1
    batches = sorted(dispatches.values(), key=lambda d: d["start"])
    return {"batches": batches, "requests": reqs, "reads": reads,
            "applied": [rec.get("failed") is None for rec in reqs],
            "window_s": t1 - t0}
