"""idle_share.stream: percent of the traced window (closed loop) in which
the device ran no operation: 100 x (1 - busy / window)."""


def read(run):
    tr = run["trace"]
    if tr is None or run["loop"] != "closed" or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
