"""idle_share.serve: percent of the traced window (open loop) in which
the device ran no operation: 100 x (1 - busy / window)."""


def read(run):
    tr = run["trace"]
    if tr is None or run["loop"] != "open" or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
