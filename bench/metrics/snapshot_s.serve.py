"""snapshot_s.serve: mean seconds of one read-snapshot refresh (the
program's ``service.snapshot`` span: fork the session and publish it), on
any thread, over the window (open loop).  None where the program opens no
such span."""


def read(run):
    tr = run["trace"]
    if tr is None or run["loop"] != "open":
        return None
    lo, hi = tr.window
    spans = [e - s for name, s, e in tr.host_events
             if name == "service.snapshot" and lo <= s and e <= hi]
    return sum(spans) / len(spans) if spans else None
