"""push_seed_s.stream: mean seconds per batch (closed loop) of the host
work the push driver adds to an update, from the program's own spans: the
``session.push_seed`` spans inside each ``session.update`` span in the
window (the read of the ranks at the batch's sources, the enumeration of
the residual's shift and its scatter).  None where the program opens no
such span, as a pull session or an older program does."""


def read(run):
    tr = run["trace"]
    if tr is None or run["loop"] != "closed":
        return None
    lo, hi = tr.window
    updates = [(s, e) for name, s, e in tr.host_events
               if name == "session.update" and lo <= s and e <= hi]
    seeds = [(s, e) for name, s, e in tr.host_events
             if name == "session.push_seed"]
    if not updates or not seeds:
        return None
    return sum(sum(pe - ps for ps, pe in seeds if s <= ps and pe <= e)
               for s, e in updates) / len(updates)
