"""The readers of the program's own spans (``session.*``, ``service.*``)
on hand-made traces: each reads its spans in the window, and reads
nothing without a trace, in the other loop, or where the program opens
no such span."""
import pytest

from bench import run, trace

METRICS = run.load_spec("rmat-s15.stream")["metrics_dir"]
SPAN_METRICS = {"update_host_s.stream": "closed",
                "update_host_s.serve": "open",
                "snapshot_s.serve": "open",
                "dispatch_idle_s.serve": "open"}


def _read(name, tr, loop):
    return run.load_reader(METRICS, name)({"trace": tr, "loop": loop})


def _trace(host_events, busy=((0.0, 10.0),)):
    return trace.Trace(window=(0.0, 10.0),
                       busy={"/device:TPU:0": trace.union(list(busy))},
                       op_s={}, spans={},
                       host_events=[("bench.window", 0.0, 10.0)]
                       + list(host_events))


# two updates in the window, 1.0 s and 2.0 s long, whose drives take
# 0.6 s and 0.5 + 0.7 s; the third update starts before the window
UPDATES = [("session.update", 1.0, 2.0), ("session.validate", 1.0, 1.1),
           ("session.drive", 1.3, 1.9),
           ("session.update", 4.0, 6.0), ("session.drive", 4.2, 4.7),
           ("session.drive", 5.0, 5.7),
           ("session.update", -1.0, 0.5), ("session.drive", -0.5, 0.4)]


@pytest.mark.parametrize("name", ["update_host_s.stream",
                                  "update_host_s.serve"])
def test_update_host_s_is_the_update_less_its_drives(name):
    got = _read(name, _trace(UPDATES), SPAN_METRICS[name])
    assert got == pytest.approx(((1.0 - 0.6) + (2.0 - 1.2)) / 2)


def test_snapshot_s_reads_every_refresh_in_the_window():
    evs = [("service.dispatch", 1.0, 3.0), ("service.snapshot", 2.8, 3.0),
           ("service.read", 4.0, 4.5), ("service.snapshot", 4.0, 4.4),
           ("service.snapshot", 9.9, 10.5)]
    assert _read("snapshot_s.serve", _trace(evs), "open") == \
        pytest.approx((0.2 + 0.4) / 2)


def test_dispatch_idle_s_is_the_device_idle_time_in_each_dispatch():
    evs = [("service.dispatch", 1.0, 3.0), ("service.dispatch", 5.0, 6.0)]
    busy = [(0.0, 1.5), (2.0, 2.5), (5.5, 8.0)]
    # idle 0.5 + 0.5 s in the first dispatch, 0.5 s in the second
    assert _read("dispatch_idle_s.serve", _trace(evs, busy), "open") == \
        pytest.approx((1.0 + 0.5) / 2)


@pytest.mark.parametrize("case", ["no trace", "other loop", "no span"])
@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_span_readers_read_nothing_without_their_spans(name, case):
    loop = SPAN_METRICS[name]
    evs = UPDATES + [("service.dispatch", 1.0, 3.0),
                     ("service.snapshot", 2.8, 3.0)]
    if case == "no trace":
        tr = None
    elif case == "other loop":
        tr, loop = _trace(evs), {"open": "closed", "closed": "open"}[loop]
    else:       # a program that opens no span of its own
        tr = _trace([("bench.update", 1.0, 2.0), ("bench.read", 4.0, 5.0)])
    assert _read(name, tr, loop) is None
