"""update_host_s.stream: mean seconds of the session's host path per
batch (closed loop), from the program's own spans: each ``session.update``
span in the window less the ``session.drive`` spans inside it, which hold
the fused driver from its launch to its one host sync.  None where the
program opens no such span."""


def read(run):
    tr = run["trace"]
    if tr is None or run["loop"] != "closed":
        return None
    lo, hi = tr.window
    updates = [(s, e) for name, s, e in tr.host_events
               if name == "session.update" and lo <= s and e <= hi]
    if not updates:
        return None
    drives = [(s, e) for name, s, e in tr.host_events
              if name == "session.drive"]
    return sum((e - s) - sum(de - ds for ds, de in drives
                             if s <= ds and de <= e)
               for s, e in updates) / len(updates)
