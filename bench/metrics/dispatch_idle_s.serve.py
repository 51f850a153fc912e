"""dispatch_idle_s.serve: mean seconds of each service dispatch (the
program's ``service.dispatch`` span) in which the device ran no operation,
over the window (open loop).  None where the program opens no such
span."""


def read(run):
    tr = run["trace"]
    if tr is None or run["loop"] != "open":
        return None
    lo, hi = tr.window
    spans = [(s, e) for name, s, e in tr.host_events
             if name == "service.dispatch" and lo <= s and e <= hi]
    if not spans:
        return None
    return sum(tr.device_idle_in(s, e) for s, e in spans) / len(spans)
